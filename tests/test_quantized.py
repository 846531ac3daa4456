"""Fixed-point p-bit pipeline: quantization, threshold LUTs, integer
kernels, and the precision="int8" engine path.

Three layers of guarantees:
  * bit-exact — the Pallas integer kernels against their jnp oracles
    (identical integer op sequences);
  * structural — LUT monotonicity (in beta down the staircase AND in the
    field along a row, the invariant the rank-count accept relies on),
    exact +-J quantization, row-index mapping;
  * statistical — precision="int8" and "f32" are different arithmetic, so
    trajectories diverge; their *ensembles* must not (EA3D residual-energy
    and flip-probability tolerance test).
"""

import warnings

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.pbit import (S41, LFSR_UNIFORM_BITS, quantize_couplings,
                             field_bound, threshold_lut, lut_accept)
from repro.core.annealing import (ArraySchedule, beta_table,
                                  beta_row_indices, ea_schedule,
                                  replica_beta_arrays)
from repro.core.lattice import build_ea3d_lattice
from repro.core.lattice_dsim import (DEFAULT_VMEM_BUDGET, LatticeDSIM,
                                     fused_brick_ceiling,
                                     fused_working_set_bytes, pick_x_tile,
                                     tiled_working_set_bytes)
from repro.compat import make_mesh, auto_axes
from repro.engines import make_engine
from repro.kernels.ops import pbit_update_int_op, pbit_sweep_int_op
from repro.kernels.ref import (pbit_brick_update_int_ref,
                               pbit_brick_sweep_int_ref)

RNG = np.random.default_rng(11)
HALF = 1 << (LFSR_UNIFORM_BITS - 1)


def make_int_inputs(shape, n_betas=3, hscale=0.1):
    Bx, By, Bz = shape
    m = jnp.asarray(RNG.choice([-1, 1], size=shape).astype(np.int8))
    s = jnp.asarray(RNG.integers(1, 2 ** 32, size=shape, dtype=np.uint32))
    h = RNG.normal(0, hscale, shape).astype(np.float32)
    w6 = [RNG.choice([-1.0, 0.0, 1.0], size=shape).astype(np.float32)
          for _ in range(6)]
    h_q, w6_q, scale = quantize_couplings(h, w6)
    lut = jnp.asarray(threshold_lut(np.linspace(0.4, 4.0, n_betas), scale,
                                    field_bound(h_q, w6_q)))
    halos = tuple(jnp.asarray(RNG.choice([-1, 1], sh).astype(np.int8))
                  for sh in [(By, Bz), (By, Bz), (Bx, Bz), (Bx, Bz),
                             (Bx, By), (Bx, By)])
    par = jnp.asarray((RNG.random(shape) < 0.5).astype(np.int8))
    return m, s, h_q, w6_q, halos, par, lut


# -- quantization -------------------------------------------------------------

def test_quantize_pm_j_exact():
    """+-J couplings quantize exactly, GCD-reduced to +-1 (scale folds it)."""
    p = build_ea3d_lattice(6, seed=0)
    h_q, w6_q, scale = quantize_couplings(p.h, p.w6)
    assert scale == 1.0
    for w, wq in zip(p.w6, w6_q):
        assert set(np.unique(np.asarray(wq))) <= {-1, 0, 1}
        np.testing.assert_array_equal(np.asarray(wq) * scale, np.asarray(w))
    assert field_bound(h_q, w6_q) == 6


def test_quantize_generic_error_bound():
    shape = (4, 4, 4)
    h = RNG.normal(0, 0.3, shape).astype(np.float32)
    w6 = [RNG.normal(0, 1.0, shape).astype(np.float32) for _ in range(6)]
    h_q, w6_q, scale = quantize_couplings(h, w6)
    for orig, q in zip([h] + w6, [h_q] + list(w6_q)):
        q = np.asarray(q, np.float64)
        assert np.abs(q).max() <= 127
        assert np.abs(q * scale - orig).max() <= scale / 2 + 1e-12


# -- threshold LUT structure --------------------------------------------------

def test_lut_monotone_in_beta():
    """Down the staircase (beta rising): thresholds fall for positive
    fields, rise for negative fields, and the zero-field column is the
    exact coin flip 2^23."""
    betas = np.arange(0.5, 5.01, 0.5)
    f_max = 6
    lut = threshold_lut(betas, 1.0, f_max).astype(np.int64)
    center = f_max
    assert (lut[:, center] == HALF).all()
    pos = lut[:, center + 1:]
    neg = lut[:, :center]
    assert (np.diff(pos, axis=0) <= 0).all()
    assert (np.diff(neg, axis=0) >= 0).all()
    assert lut.min() >= 0 and lut.max() <= (1 << LFSR_UNIFORM_BITS)


def test_lut_monotone_in_field_rowwise():
    """Each row nonincreasing in the field — the rank-count invariant."""
    lut = threshold_lut(np.arange(0.5, 5.01, 0.5), 0.03, 50,
                        fmt=S41).astype(np.int64)
    assert (np.diff(lut, axis=1) <= 0).all()


def test_lut_rejects_negative_beta():
    with pytest.raises(ValueError):
        threshold_lut([-0.5, 1.0], 1.0, 4)


@pytest.mark.parametrize("width", [13, 201])
def test_lut_accept_equals_direct_lookup(width):
    """Rank-count accept (narrow) and gather fallback (wide) both equal the
    definition u >= thr[field + f_off]."""
    f_max = (width - 1) // 2
    thr = jnp.asarray(threshold_lut([1.3], 1.0 / max(f_max, 1), f_max)[0])
    field = jnp.asarray(RNG.integers(-f_max, f_max + 1, size=(9, 7)),
                        jnp.int32)
    u = jnp.asarray(RNG.integers(0, 1 << LFSR_UNIFORM_BITS, size=(9, 7),
                                 dtype=np.uint32))
    got = np.asarray(lut_accept(thr, field, f_max, u))
    want = np.asarray(u) >= np.asarray(thr)[np.asarray(field) + f_max]
    np.testing.assert_array_equal(got, want)


# -- staircase -> row indices -------------------------------------------------

def test_beta_row_indices_round_trip():
    sch = ea_schedule(100)
    arr = sch.beta_array()
    table = beta_table(arr)
    rows = beta_row_indices(arr, table)
    np.testing.assert_array_equal(table[rows], arr)
    # per-replica fans map elementwise, any shape
    bR = replica_beta_arrays(sch, 4, spread=0.25)
    tR = beta_table(bR)
    rR = beta_row_indices(bR, tR)
    assert rR.shape == bR.shape and rR.dtype == np.int32
    np.testing.assert_array_equal(tR[rR], bR)


def test_beta_row_indices_unknown_beta_rejected():
    with pytest.raises(ValueError):
        beta_row_indices(np.array([0.5, 0.7]), np.array([0.5, 1.0]))


def test_array_schedule_preserves_dtype_and_shape():
    rows = np.arange(12, dtype=np.int32).reshape(6, 2)
    sched = ArraySchedule(rows)
    assert sched.total_sweeps == 6
    assert sched.beta_array().dtype == np.int32


# -- integer kernels vs jnp oracles (bit-exact) -------------------------------

@pytest.mark.parametrize("shape,bx", [
    ((8, 4, 4), 2), ((8, 4, 4), 8), ((16, 8, 8), 4), ((6, 3, 5), 3),
])
def test_int_update_kernel_matches_ref(shape, bx):
    m, s, h_q, w6_q, halos, par, lut = make_int_inputs(shape)
    m1, s1 = pbit_update_int_op(m, s, 1, par, h_q, w6_q, halos, lut, bx=bx,
                                impl="interpret")
    m2, s2 = pbit_brick_update_int_ref(m, s, 1, par, h_q, w6_q, halos, lut)
    assert (np.asarray(m1) == np.asarray(m2)).all()
    assert (np.asarray(s1) == np.asarray(s2)).all()


def test_int_sweep_kernel_matches_ref_and_per_phase():
    shape = (8, 4, 4)
    m, s, h_q, w6_q, halos, par, lut = make_int_inputs(shape)
    masks = np.zeros((2,) + shape, np.int8)
    masks[0][(np.indices(shape).sum(0) % 2) == 0] = 1
    masks[1] = 1 - masks[0]
    masks = jnp.asarray(masks)
    rows = jnp.asarray([0, 2, 1, 2], jnp.int32)
    got = pbit_sweep_int_op(m, s, rows, masks, h_q, w6_q, halos, lut,
                            impl="interpret")
    want = pbit_brick_sweep_int_ref(m, s, rows, masks, h_q, w6_q, halos, lut)
    for a, b in zip(got, want):
        assert (np.asarray(a) == np.asarray(b)).all()
    # the fused launch == chained per-phase launches (both Pallas)
    mc, sc = m, s
    fl = 0
    for t in range(rows.shape[0]):
        for c in range(2):
            m2, sc = pbit_update_int_op(mc, sc, rows[t], masks[c], h_q,
                                        w6_q, halos, lut, impl="interpret")
            fl += int((np.asarray(m2) != np.asarray(mc)).sum())
            mc = m2
    assert (np.asarray(got[0]) == np.asarray(mc)).all()
    assert (np.asarray(got[1]) == np.asarray(sc)).all()
    assert int(got[2]) == fl


def test_int_engine_ref_vs_interpret_bitexact():
    """The whole int8 engine path agrees bit-for-bit between the jnp
    oracle impl and the Pallas interpreter impl."""
    prob = build_ea3d_lattice(4, seed=3)
    mesh = make_mesh((1,), ("data",), axis_types=auto_axes(1))
    outs = []
    for impl in ("ref", "interpret"):
        eng = LatticeDSIM(prob, mesh, dim_axes=("data", None, None),
                          precision="int8", impl=impl)
        st = eng.init_state(seed=5)
        st, _ = eng.run_recorded(st, ea_schedule(8), [8], sync_every=4)
        outs.append(st)
    assert (np.asarray(outs[0].m) == np.asarray(outs[1].m)).all()
    assert (np.asarray(outs[0].s) == np.asarray(outs[1].s)).all()


# -- statistical equivalence int8 vs f32 --------------------------------------

def test_int8_statistically_matches_f32_ea3d():
    """Same EA3D instance, same schedule, R independent replicas per
    precision: mean final (annealed) energy and aggregate flip probability
    must agree within ensemble tolerance.  (On +-J the quantization is
    exact, so the only difference is tanh-rounding in the accept rule —
    trajectories diverge chaotically but the ensembles must not.)"""
    R, SW = 6, 240
    res = {}
    for prec in ("f32", "int8"):
        h = make_engine("lattice", L=6, seed=7, impl="ref", replicas=R,
                        precision=prec)
        st = h.init_state(seed=1)
        st, rec = h.run_recorded(st, ea_schedule(SW), [SW], sync_every=4)
        res[prec] = (float(np.asarray(rec.energies[-1]).mean()), rec.flips)
    e_f32, fl_f32 = res["f32"]
    e_i8, fl_i8 = res["int8"]
    assert e_f32 < 0 and e_i8 < 0
    assert abs(e_i8 - e_f32) / abs(e_f32) < 0.05
    assert abs(fl_i8 - fl_f32) / fl_f32 < 0.10


def test_int8_flip_probability_matches_f32_at_fixed_beta():
    """Per-site flip probability over many sweeps at constant beta."""
    from repro.core.annealing import constant_schedule
    R, SW, L = 4, 200, 6
    prob = {}
    for prec in ("f32", "int8"):
        h = make_engine("lattice", L=L, seed=3, impl="ref", replicas=R,
                        precision=prec)
        st = h.init_state(seed=2)
        st, rec = h.run_recorded(st, constant_schedule(1.0, SW), [SW],
                                 sync_every=4)
        prob[prec] = rec.flips / (L ** 3 * R * SW)
    assert 0.02 < prob["f32"] < 0.95
    assert abs(prob["int8"] - prob["f32"]) < 0.02


def test_dsim_int8_statistically_matches_f32():
    from repro.core.graph import ea3d
    from repro.core.coloring import lattice3d_coloring
    from repro.core.partition import slab_partition
    g = ea3d(6, seed=7)
    col = lattice3d_coloring(6)
    labels = slab_partition(6, 2)
    means = {}
    for prec in ("f32", "int8"):
        h = make_engine("dsim", g, coloring=col, K=2, labels=labels,
                        rng="lfsr", precision=prec, replicas=4)
        st = h.init_state(seed=0)
        st, rec = h.run_recorded(st, ea_schedule(200), [200], sync_every=4)
        means[prec] = float(np.asarray(rec.energies[-1]).mean())
    assert means["int8"] < 0
    assert abs(means["int8"] - means["f32"]) / abs(means["f32"]) < 0.05


# -- per-replica staircases on the integer path -------------------------------

def test_per_replica_staircase_rides_int8_path():
    R = 3
    sch = ea_schedule(48)
    bR = replica_beta_arrays(sch, R, spread=0.3)
    outs = {}
    for prec in ("f32", "int8"):
        h = make_engine("lattice", L=6, seed=7, impl="ref", replicas=R,
                        precision=prec)
        st = h.init_state(seed=0)
        st, rec = h.eng.run_recorded_full(st, sch, [48], sync_every=4,
                                          betas_R=bR)
        outs[prec] = np.asarray(rec.energies[-1])
    assert outs["int8"].shape == (R,)
    # the annealing-rate fan actually differentiates the replicas
    assert len(np.unique(outs["int8"])) > 1
    # and the fanned ensembles agree across precisions
    assert abs(outs["int8"].mean() - outs["f32"].mean()) \
        / abs(outs["f32"].mean()) < 0.05


# -- VMEM working-set decision ------------------------------------------------

def test_fused_fallback_warns_and_is_exposed():
    prob = build_ea3d_lattice(6, seed=0)
    mesh = make_mesh((1,), ("data",), axis_types=auto_axes(1))
    budget = 1 << 18          # below the fused 6^3 f32 brick, above a slab
    with pytest.warns(RuntimeWarning, match="falling back"):
        eng = LatticeDSIM(prob, mesh, dim_axes=("data", None, None),
                          impl="ref", vmem_budget_bytes=budget)
    assert eng.kernel_path == "per_phase"
    assert eng.fallback_reason == "vmem"
    assert eng.fused_requested and not eng.fused
    assert tiled_working_set_bytes(eng.brick, eng.kernel_bx, "f32") <= budget
    # the fallback engine still runs (per-phase dispatch)
    st = eng.init_state(seed=0)
    st, rec = eng.run_recorded(st, ea_schedule(8), [8], sync_every=4)
    assert float(np.asarray(rec.energies[-1])) < 0


def test_fused_decision_default_budget_and_handle_exposure():
    with warnings.catch_warnings():
        warnings.simplefilter("error")           # no warning expected
        h = make_engine("lattice", L=6, seed=0, impl="ref")
    assert h.kernel_path == "fused"
    assert h.precision == "f32"
    h2 = make_engine("lattice", L=6, seed=0, impl="ref", precision="int8",
                     vmem_budget_bytes=1 << 19)  # 512 KiB: 6^3 int8 fits
    assert h2.kernel_path == "fused" and h2.precision == "int8"


def test_int8_raises_fused_brick_ceiling():
    """The point of the exercise: the quantized working set is smaller, so
    the same VMEM budget admits a strictly larger fused brick."""
    for n_c in (2, 3):
        assert fused_brick_ceiling(n_c, "int8") > fused_brick_ceiling(n_c,
                                                                      "f32")
    assert fused_brick_ceiling(2, "int8") >= 72      # tile-padded, 16 MiB
    b = (32, 32, 32)
    assert fused_working_set_bytes(b, 3, "int8") < \
        fused_working_set_bytes(b, 3, "f32")


@pytest.mark.parametrize("kernel,bx", [("int8", 25), ("f32", 10),
                                       ("energy", 10)])
def test_over_budget_fallback_tile_fits_budget(kernel, bx):
    """At the paper's one-chip brick (100^3) the fused kernel is over the
    default budget; the fallback tile is the largest divisor of 100 whose
    double-buffered slab fits, and twice that tile does not."""
    brick = (100, 100, 100)
    assert fused_working_set_bytes(brick, 2, "int8") > DEFAULT_VMEM_BUDGET
    assert pick_x_tile(brick, kernel) == bx
    assert tiled_working_set_bytes(brick, bx, kernel) <= DEFAULT_VMEM_BUDGET
    assert tiled_working_set_bytes(brick, 2 * bx, kernel) > \
        DEFAULT_VMEM_BUDGET


def test_int8_engine_fallback_picks_fitting_tile():
    prob = build_ea3d_lattice(8, seed=1)
    mesh = make_mesh((1,), ("data",), axis_types=auto_axes(1))
    budget = 1 << 19          # 8^3 int8 fused needs more; a 2-plane slab fits
    with pytest.warns(RuntimeWarning, match="x-tiles of"):
        eng = LatticeDSIM(prob, mesh, dim_axes=("data", None, None),
                          precision="int8", impl="ref",
                          vmem_budget_bytes=budget)
    assert eng.kernel_path == "per_phase" and eng.fallback_reason == "vmem"
    assert eng.fused_working_set > budget
    assert 8 % eng.kernel_bx == 0
    assert tiled_working_set_bytes(eng.brick, eng.kernel_bx, "int8") <= budget
    with pytest.raises(ValueError, match="no x-tile"):
        LatticeDSIM(prob, mesh, dim_axes=("data", None, None),
                    precision="int8", impl="ref", vmem_budget_bytes=1024)


# -- registry guards ----------------------------------------------------------

def test_wide_lut_rejected_on_pallas_impl():
    """Non-GCD-reducible couplings widen the LUT past the rank-count cap;
    the pallas target must refuse at init, not fail at first lowering."""
    import dataclasses
    base = build_ea3d_lattice(4, seed=0)
    wide = dataclasses.replace(
        base, h=jnp.asarray(RNG.normal(0, 1.0, base.dims), jnp.float32))
    mesh = make_mesh((1,), ("data",), axis_types=auto_axes(1))
    with pytest.raises(ValueError, match="rank-count"):
        LatticeDSIM(wide, mesh, dim_axes=("data", None, None),
                    precision="int8", impl="pallas")
    # the jnp paths keep working (gather fallback)
    eng = LatticeDSIM(wide, mesh, dim_axes=("data", None, None),
                      precision="int8", impl="ref")
    st = eng.init_state(seed=0)
    st, rec = eng.run_recorded(st, ea_schedule(8), [8], sync_every=4)
    assert np.isfinite(float(np.asarray(rec.energies[-1])))


def test_registry_precision_guards():
    from repro.core.graph import ea3d
    from repro.core.coloring import lattice3d_coloring
    g = ea3d(4, seed=0)
    col = lattice3d_coloring(4)
    with pytest.raises(ValueError):
        make_engine("gibbs", g, coloring=col, precision="int8")
    with pytest.raises(ValueError):
        make_engine("lattice", L=4, precision="fp4")
    with pytest.raises(ValueError):
        make_engine("dsim", g, coloring=col, K=2,
                    labels=np.zeros(g.n, np.int32), rng="philox",
                    precision="int8")
