"""Bit-plane replica engine: multi-spin-coded sweeps over the multi-word
lane fabric (32 lanes per uint32 word plane, W = ceil(R/32) stacked
planes).

Three layers of guarantees, mirroring tests/test_quantized.py:
  * bit-exact — the Pallas word kernel against its jnp oracle, and lane r
    of the word math against replica r of the int8 integer pipeline
    (multi-spin coding changes the layout, never the dynamics);
  * structural — lane pack/unpack identities, the carry-save ones count,
    registry/scheduler guards (clear errors, lane clamping), the VMEM
    working-set model;
  * statistical — every packed lane is an independent chain: per-lane
    EA3D energy trajectories match the int8 engine for all 32 lanes
    individually, lanes are prefix-stable in R, and a packed lane's
    trajectory depends only on its own seed.
"""

import warnings

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.annealing import ea_schedule, replica_beta_arrays
from repro.core.lattice import build_ea3d_lattice
from repro.core.lattice_dsim import (BitplaneLatticeState, LatticeDSIM,
                                     fused_brick_ceiling,
                                     fused_working_set_bytes)
from repro.core.packing import (LANE_WIDTH, MAX_LANE_WORDS, lane_words,
                                pack_lanes, unpack_lanes)
from repro.core.pbit import (bitplane_planes, field_bound, quantize_couplings,
                             threshold_lut)
from repro.compat import make_mesh, auto_axes
from repro.engines import make_engine
from repro.engines.base import check_precision, lanes_of
from repro.kernels.ops import pbit_bitplane_sweep_op
from repro.kernels.ref import (bitplane_ones_count_ref,
                               pbit_bitplane_sweep_ref,
                               pbit_brick_sweep_int_ref)

RNG = np.random.default_rng(23)


def make_bitplane_inputs(shape, R, n_betas=3, with_h=True):
    """Random +-J-style brick in both layouts: per-replica int8 arrays and
    the packed word forms, sharing one quantized problem."""
    Bx, By, Bz = shape
    m = RNG.choice([-1, 1], size=(R,) + shape).astype(np.int8)
    s = RNG.integers(1, 2 ** 32, size=(R,) + shape, dtype=np.uint32)
    h = (RNG.choice([-1.0, 0.0, 1.0], size=shape) if with_h
         else np.zeros(shape)).astype(np.float32)
    w6 = [RNG.choice([-1.0, 0.0, 1.0], size=shape).astype(np.float32)
          for _ in range(6)]
    h_q, w6_q, scale = quantize_couplings(h, w6)
    lut = jnp.asarray(threshold_lut(np.linspace(0.4, 4.0, n_betas), scale,
                                    field_bound(h_q, w6_q)))
    halos = [RNG.choice([-1, 1], (R,) + sh).astype(np.int8) for sh in
             [(By, Bz), (By, Bz), (Bx, Bz), (Bx, Bz), (Bx, By), (Bx, By)]]
    masks = np.zeros((2,) + shape, np.int8)
    masks[0][(np.indices(shape).sum(0) % 2) == 0] = 1
    masks[1] = 1 - masks[0]
    signs6, nz6, base, _ = bitplane_planes(h_q, w6_q)
    # per-word live-lane masks: full words all-ones, the tail word masks
    # its dead lanes (mirrors LatticeDSIM's lane_masks construction)
    W = lane_words(R)
    last = R - (W - 1) * LANE_WIDTH
    lane_masks = np.full((W,), 0xFFFFFFFF, np.uint64)
    if last < LANE_WIDTH:
        lane_masks[-1] = (1 << last) - 1
    lane_masks = lane_masks.astype(np.uint32)
    masks_w = jnp.asarray(
        np.where(masks[:, None] != 0,
                 lane_masks[None, :, None, None, None], 0).astype(np.uint32))
    mw = pack_lanes(jnp.asarray(m))
    halos_w = tuple(pack_lanes(jnp.asarray(hh)) for hh in halos)
    return dict(m=m, s=s, h_q=h_q, w6_q=w6_q, lut=lut, halos=halos,
                masks=jnp.asarray(masks), signs6=signs6, nz6=nz6, base=base,
                masks_w=masks_w, mw=mw, halos_w=halos_w)


# -- bit-exact: lanes == int8 replicas ----------------------------------------

@pytest.mark.parametrize("shape,R", [
    ((6, 4, 4), 1), ((6, 4, 4), 7), ((4, 4, 4), 32), ((5, 3, 4), 13),
    ((4, 3, 3), 40), ((4, 3, 3), 64),
])
def test_bitplane_oracle_matches_int8_per_lane(shape, R):
    """Lane r (word r//32, bit r%32) of the word oracle is bit-identical
    (spins, LFSR, flips) to replica r of the int8 reference — multi-spin
    coding is a layout, not a different sampler — including lane counts
    that straddle into a second word plane."""
    d = make_bitplane_inputs(shape, R)
    rows = jnp.asarray([0, 2, 1], jnp.int32)
    mw2, s2, fl2 = pbit_bitplane_sweep_ref(
        d["mw"], jnp.asarray(d["s"]), rows, d["masks_w"], d["signs6"],
        d["nz6"], d["base"], d["halos_w"], d["lut"])
    m_un = np.asarray(unpack_lanes(mw2, R))
    for r in range(R):
        mr, sr, fl = pbit_brick_sweep_int_ref(
            jnp.asarray(d["m"][r]), jnp.asarray(d["s"][r]), rows,
            d["masks"], d["h_q"], d["w6_q"],
            tuple(jnp.asarray(hh[r]) for hh in d["halos"]), d["lut"])
        assert (m_un[r] == np.asarray(mr)).all()
        assert (np.asarray(s2)[r] == np.asarray(sr)).all()
        assert int(fl2[r]) == int(fl)


@pytest.mark.parametrize("shape,R", [((6, 4, 4), 3), ((4, 4, 4), 8),
                                     ((4, 3, 3), 34)])
def test_bitplane_kernel_matches_oracle(shape, R):
    """The Pallas word kernel (interpreter) against the jnp oracle —
    identical integer op outcomes, including per-lane flip counts; the
    W=2 case exercises the word loop in the op dispatch."""
    d = make_bitplane_inputs(shape, R)
    rows = jnp.asarray([1, 0, 2, 2], jnp.int32)
    want = pbit_bitplane_sweep_ref(
        d["mw"], jnp.asarray(d["s"]), rows, d["masks_w"], d["signs6"],
        d["nz6"], d["base"], d["halos_w"], d["lut"])
    got = pbit_bitplane_sweep_op(
        d["mw"], jnp.asarray(d["s"]), rows, d["masks_w"], d["signs6"],
        d["nz6"], d["base"], d["halos_w"], d["lut"], impl="interpret")
    for a, b in zip(got, want):
        assert (np.asarray(a) == np.asarray(b)).all()


def test_bitplane_kernel_per_lane_rows():
    """A (S, R) per-lane staircase fan flows through both impls
    identically — each lane reads its own LUT row."""
    R = 5
    d = make_bitplane_inputs((4, 4, 4), R)
    rows = jnp.asarray(RNG.integers(0, 3, size=(3, R)), jnp.int32)
    want = pbit_bitplane_sweep_ref(
        d["mw"], jnp.asarray(d["s"]), rows, d["masks_w"], d["signs6"],
        d["nz6"], d["base"], d["halos_w"], d["lut"])
    got = pbit_bitplane_sweep_op(
        d["mw"], jnp.asarray(d["s"]), rows, d["masks_w"], d["signs6"],
        d["nz6"], d["base"], d["halos_w"], d["lut"], impl="interpret")
    for a, b in zip(got, want):
        assert (np.asarray(a) == np.asarray(b)).all()
    # and the fan actually differentiates lanes: identical lane states,
    # different rows -> different trajectories
    d2 = make_bitplane_inputs((4, 4, 4), 2)
    same = np.broadcast_to(d2["s"][:1], d2["s"].shape).copy()
    mw_same = pack_lanes(jnp.asarray(
        np.broadcast_to(d2["m"][:1], d2["m"].shape).copy()))
    fan = jnp.asarray([[0, 2]] * 6, jnp.int32)
    mw3, _, _ = pbit_bitplane_sweep_ref(
        mw_same, jnp.asarray(same), fan, d2["masks_w"], d2["signs6"],
        d2["nz6"], d2["base"], d2["halos_w"], d2["lut"])
    lanes = np.asarray(unpack_lanes(mw3, 2))
    assert (lanes[0] != lanes[1]).any()


def test_bitplane_ones_count_matches_popcount():
    """The carry-save adder tree's 3 bit-slices equal the per-lane sum of
    contribution bits, for every lane of every site."""
    R = LANE_WIDTH
    d = make_bitplane_inputs((4, 3, 3), R)
    # the CSA tree is a ONE-WORD primitive: feed it word plane 0
    b0, b1, b2 = bitplane_ones_count_ref(
        d["mw"][0], d["signs6"], d["nz6"],
        tuple(h[0] for h in d["halos_w"]))
    cnt = (np.asarray(unpack_lanes(b0[None], R)) > 0).astype(np.int64) \
        + 2 * (np.asarray(unpack_lanes(b1[None], R)) > 0) \
        + 4 * (np.asarray(unpack_lanes(b2[None], R)) > 0)
    # direct recount from the unpacked layout
    from repro.kernels.ref import _shifted_int
    want = np.zeros((R,) + (4, 3, 3), np.int64)
    for r in range(R):
        nbs = _shifted_int(jnp.asarray(d["m"][r]),
                           tuple(jnp.asarray(hh[r]) for hh in d["halos"]))
        for nb, w in zip(nbs, d["w6_q"]):
            wq = np.asarray(w, np.int64)
            want[r] += ((np.asarray(nb, np.int64) * wq > 0) & (wq != 0))
    np.testing.assert_array_equal(cnt, want)


# -- engine layer -------------------------------------------------------------

def test_engine_ref_vs_interpret_bitexact():
    outs = []
    for impl in ("ref", "interpret"):
        h = make_engine("lattice", L=4, seed=3, impl=impl, replicas=3,
                        precision="bitplane")
        st = h.init_state(seed=5)
        st, _ = h.run_recorded(st, ea_schedule(8), [8], sync_every=4)
        outs.append(st)
    assert (np.asarray(outs[0].m) == np.asarray(outs[1].m)).all()
    assert (np.asarray(outs[0].s) == np.asarray(outs[1].s)).all()


def test_bitplane_engine_matches_int8_all_32_lanes():
    """The acceptance gate: EA3D energy trajectories of the bit-plane
    engine equal the int8 engine's per replica, for all 32 lanes
    individually, at matched seeds and schedules — and every lane anneals
    (statistical sanity on top of the exact match)."""
    R, SW = LANE_WIDTH, 96
    rec_pts = [32, 64, 96]
    res = {}
    for prec in ("int8", "bitplane"):
        h = make_engine("lattice", L=6, seed=7, impl="ref", replicas=R,
                        precision=prec)
        st = h.init_state(seed=1)
        st, rec = h.run_recorded(st, ea_schedule(SW), rec_pts, sync_every=4)
        res[prec] = (np.asarray(rec.energies), rec.flips,
                     np.asarray(h.global_spins(st)))
    e_bp, fl_bp, spins_bp = res["bitplane"]
    e_i8, fl_i8, spins_i8 = res["int8"]
    assert e_bp.shape == (len(rec_pts), R)
    for r in range(R):
        np.testing.assert_allclose(e_bp[:, r], e_i8[:, r], rtol=0, atol=0)
        assert e_bp[-1, r] < 0                      # every lane annealed
    assert fl_bp == fl_i8
    np.testing.assert_array_equal(spins_bp, spins_i8)


def test_bitplane_engine_matches_int8_at_two_words():
    """The W=2 acceptance gate: at R=64 every lane of the stacked word
    planes is bit-identical to its int8 replica — spins, energies, and
    flip totals — so the word loop over planes changes nothing about the
    dynamics."""
    R, SW = 2 * LANE_WIDTH, 48
    res = {}
    for prec in ("int8", "bitplane"):
        h = make_engine("lattice", L=4, seed=7, impl="ref", replicas=R,
                        precision=prec)
        st = h.init_state(seed=1)
        st, rec = h.run_recorded(st, ea_schedule(SW), [24, 48],
                                 sync_every=4)
        res[prec] = (np.asarray(rec.energies), rec.flips,
                     np.asarray(h.global_spins(st)))
    e_bp, fl_bp, spins_bp = res["bitplane"]
    e_i8, fl_i8, spins_i8 = res["int8"]
    assert e_bp.shape == (2, R)
    np.testing.assert_array_equal(e_bp, e_i8)
    assert fl_bp == fl_i8
    np.testing.assert_array_equal(spins_bp, spins_i8)


def test_lane_prefix_stability():
    """Replica r of (seed, R) equals replica r of (seed, R') — growing the
    packed batch never reshuffles existing lanes (the spawn_seeds
    contract, preserved through the word layout) — in the bit index AND
    across word-plane boundaries (R=33 vs R=64)."""
    e = {}
    for R in (8, 32, 33, 64):
        h = make_engine("lattice", L=4, seed=0, impl="ref", replicas=R,
                        precision="bitplane")
        st = h.init_state(seed=9)
        st, rec = h.run_recorded(st, ea_schedule(16), [16], sync_every=4)
        e[R] = np.asarray(rec.energies[-1])
    np.testing.assert_array_equal(e[8], e[32][:8])
    np.testing.assert_array_equal(e[32], e[64][:32])
    np.testing.assert_array_equal(e[33], e[64][:33])


def test_packed_lane_depends_only_on_its_seed():
    """init_state_packed: a lane's trajectory is bitwise independent of
    its batch-mates (the replica-packing contract on the word layout)."""
    seeds = [11, 222, 3333]
    h3 = make_engine("lattice", L=4, seed=0, impl="ref", replicas=3,
                     precision="bitplane")
    st = h3.init_state_packed(seeds)
    st, rec3 = h3.run_recorded(st, ea_schedule(16), [16], sync_every=4)
    h1 = make_engine("lattice", L=4, seed=0, impl="ref", replicas=1,
                     precision="bitplane")
    s1 = h1.init_state_packed([seeds[1]])
    s1, rec1 = h1.run_recorded(s1, ea_schedule(16), [16], sync_every=4)
    assert float(rec3.energies[-1][1]) == float(rec1.energies[-1][0])


def test_per_replica_staircase_fan_rides_bitplane():
    R = 4
    sch = ea_schedule(48)
    bR = replica_beta_arrays(sch, R, spread=0.3)
    outs = {}
    for prec in ("int8", "bitplane"):
        h = make_engine("lattice", L=6, seed=7, impl="ref", replicas=R,
                        precision=prec)
        st = h.init_state(seed=0)
        st, rec = h.eng.run_recorded_full(st, sch, [48], sync_every=4,
                                          betas_R=bR)
        outs[prec] = np.asarray(rec.energies[-1])
    assert outs["bitplane"].shape == (R,)
    assert len(np.unique(outs["bitplane"])) > 1     # the fan differentiates
    np.testing.assert_array_equal(outs["bitplane"], outs["int8"])


def test_snapshot_restore_bitwise_resume():
    h = make_engine("lattice", L=4, seed=0, impl="ref", replicas=4,
                    precision="bitplane")
    st = h.init_state(seed=2)
    st, _ = h.run_recorded(st, ea_schedule(16), [8], sync_every=4)
    st2 = h.restore(h.snapshot(st))
    assert isinstance(st2, BitplaneLatticeState)
    a, ra = h.run_recorded(st, ea_schedule(16), [8], sync_every=4)
    b, rb = h.run_recorded(st2, ea_schedule(16), [8], sync_every=4)
    assert (np.asarray(a.m) == np.asarray(b.m)).all()
    np.testing.assert_array_equal(np.asarray(ra.energies),
                                  np.asarray(rb.energies))


def test_bitplane_multi_device_halo_exchange():
    """On an x-sharded 2-device mesh, lane r of the bit-plane engine stays
    bit-identical to replica r of the int8 engine: the word halo planes
    crossing the ppermute carry exactly what the int8 exchange carries
    (same boundary-staleness semantics, 8x smaller payload) — at R=5
    (one word) and R=40 (two stacked word planes crossing the wire),
    across exchange cadences.  (k=1 vs k=2 differ BY DESIGN —
    cross-device neighbors see sync_every-stale halos — so the gate is
    cross-precision at equal mesh, not cross-mesh.)"""
    import os
    import subprocess
    import sys
    import textwrap
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent("""
        import numpy as np
        from repro.core.lattice import build_ea3d_lattice
        from repro.core.lattice_dsim import LatticeDSIM
        from repro.core.packing import unpack_lanes
        from repro.core.annealing import ea_schedule
        from repro.compat import make_mesh, auto_axes
        prob = build_ea3d_lattice(6, seed=4)
        mesh = make_mesh((2,), ("x",), axis_types=auto_axes(1))
        for R, sync in ((5, 4), (40, 1), (40, 4)):
            outs = {}
            for prec in ("int8", "bitplane"):
                eng = LatticeDSIM(prob, mesh, dim_axes=("x", None, None),
                                  precision=prec, impl="ref", replicas=R)
                st = eng.init_state(seed=3)
                st, rec = eng.run_recorded(st, ea_schedule(24), [24],
                                           sync_every=sync)
                m = np.asarray(unpack_lanes(st.m, R)) \\
                    if prec == "bitplane" else np.asarray(st.m)
                outs[prec] = (m, np.asarray(st.s),
                              np.asarray(rec.energies[-1]))
            for a, b in zip(outs["bitplane"], outs["int8"]):
                assert (a == b).all(), (R, sync)
        print("DIST-BITWISE OK")
    """)], capture_output=True, text=True, env=env, timeout=420)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "DIST-BITWISE OK" in r.stdout


# -- working-set model --------------------------------------------------------

def test_bitplane_working_set_per_lane_beats_int8():
    """Per replica-lane, the word layout is the densest of the three
    pipelines — the whole point of multi-spin coding."""
    b = (32, 32, 32)
    for n_c in (2, 3):
        per_lane_bp = fused_working_set_bytes(b, n_c, "bitplane",
                                              lanes=32) / 32
        per_rep_i8 = fused_working_set_bytes(b, n_c, "int8")
        assert per_lane_bp < per_rep_i8
    # z rides the 128 lanes, so small bricks pay lane padding: 16^3 at R=32
    assert fused_brick_ceiling(3, "bitplane", lanes=32) >= 16


def test_bitplane_over_ceiling_raises():
    """The word kernel has no per-phase fallback: a brick over its VMEM
    ceiling is refused at construction, naming the ceiling."""
    prob = build_ea3d_lattice(6, seed=0)
    mesh = make_mesh((1,), ("data",), axis_types=auto_axes(1))
    ceiling = fused_brick_ceiling(prob.n_colors, "bitplane", 1 << 20,
                                  lanes=32)
    assert ceiling < 6
    with pytest.raises(ValueError, match=rf"ceiling is a {ceiling}\^3"):
        LatticeDSIM(prob, mesh, dim_axes=("data", None, None),
                    precision="bitplane", impl="ref", replicas=32,
                    vmem_budget_bytes=1 << 20)
    eng = LatticeDSIM(prob, mesh, dim_axes=("data", None, None),
                      precision="bitplane", impl="ref", replicas=32)
    assert eng.kernel_path == "bitplane"
    st = eng.init_state(seed=0)
    st, rec = eng.run_recorded(st, ea_schedule(8), [8], sync_every=4)
    assert float(np.asarray(rec.energies[-1]).min()) < 0


# -- guards -------------------------------------------------------------------

def test_registry_guards():
    from repro.core.graph import ea3d
    from repro.core.coloring import lattice3d_coloring
    g = ea3d(4, seed=0)
    col = lattice3d_coloring(4)
    for eng_name in ("gibbs", "dsim"):
        with pytest.raises(ValueError, match="lattice/dsim_dist path"):
            make_engine(eng_name, g, coloring=col, K=2,
                        labels=np.zeros(g.n, np.int32),
                        precision="bitplane")
    cap = MAX_LANE_WORDS * LANE_WIDTH
    with pytest.raises(ValueError, match=rf"\[1, {cap}\]"):
        make_engine("lattice", L=4, precision="bitplane", replicas=cap + 1)
    # word-straddling replica counts are legal now (the multi-word fabric)
    h = make_engine("lattice", L=4, precision="bitplane", replicas=33,
                    impl="ref")
    assert h.eng.words == 2
    with pytest.raises(ValueError, match="kernel_bx"):
        make_engine("lattice", L=4, precision="bitplane", kernel_bx=2)
    assert lanes_of("bitplane") == LANE_WIDTH and lanes_of("int8") == 1
    check_precision("lattice", "bitplane")          # allowed


def test_non_sign_couplings_rejected():
    """Problems whose couplings don't quantize to +-1/0 have no sign plane
    — a clear init error pointing at int8, not a packing shape error."""
    import dataclasses
    base = build_ea3d_lattice(4, seed=0)
    wide = dataclasses.replace(
        base, h=jnp.asarray(RNG.normal(0, 1.0, base.dims), jnp.float32))
    mesh = make_mesh((1,), ("data",), axis_types=auto_axes(1))
    with pytest.raises(ValueError):
        LatticeDSIM(wide, mesh, dim_axes=("data", None, None),
                    precision="bitplane", impl="ref")


# -- serving layer ------------------------------------------------------------

def test_scheduler_clamps_bitplane_to_lane_multiples():
    from repro.serve.scheduler import ReplicaPackingScheduler
    from repro.serve.jobs import Job, JobSpec, schedule_fingerprint
    sch = ea_schedule(32)
    fp = schedule_fingerprint(sch)

    def job(seq, replicas, precision):
        spec = JobSpec(problem="p", engine="lattice", sweeps=32,
                       replicas=replicas, precision=precision)
        return Job(f"j{seq}", seq, spec, "lat:L=6:seed=0", sch, fp, 0.0)

    s = ReplicaPackingScheduler(max_replicas_per_call=64)
    # two bitplane jobs coalesce and execute at the full 32-lane word
    b = s.next_batch([job(0, 4, "bitplane"), job(1, 8, "bitplane")])
    assert len(b.jobs) == 2 and b.r_exec == 32
    # a word-straddling pack clamps to the next word multiple, not pow2
    b = s.next_batch([job(0, 20, "bitplane"), job(1, 20, "bitplane")])
    assert len(b.jobs) == 2 and b.r_exec == 64       # W=2, not one word
    # the budget still bounds the pack (cap 64 here -> at most two words)
    b = s.next_batch([job(0, 40, "bitplane"), job(1, 40, "bitplane")])
    assert len(b.jobs) == 1 and b.r_exec == 64
    assert s.replica_budget("bitplane") == 64
    wide = ReplicaPackingScheduler(max_replicas_per_call=1024)
    assert wide.replica_budget("bitplane") == 32 * MAX_LANE_WORDS
    # bitplane never packs with int8 (precision is in the pack key)
    b = s.next_batch([job(0, 4, "bitplane"), job(1, 4, "int8")])
    assert len(b.jobs) == 1
    # prewarm bucketing agrees with batch formation: word multiples,
    # R=33 and R=64 bucket to the SAME W=2 executable
    assert s.r_exec_for("lattice", 4, "bitplane") == 32
    assert s.r_exec_for("lattice", 33, "bitplane") == 64
    assert s.r_exec_for("lattice", 64, "bitplane") == 64
    assert wide.r_exec_for("lattice", 65, "bitplane") == 96   # not pow2 128
    assert s.r_exec_for("lattice", 4, "int8") == 4
    # a cap below the word width just runs unpadded
    tight = ReplicaPackingScheduler(max_replicas_per_call=16)
    b = tight.next_batch([job(0, 3, "bitplane")])
    assert b.r_exec == 4                             # pow2 pad only


def test_server_bitplane_jobs_pack_and_guard():
    from repro.core.graph import ea3d
    from repro.core.coloring import lattice3d_coloring
    from repro.core.partition import slab_partition
    from repro.serve.server import SampleServer
    srv = SampleServer(pack=True, warm_compile=False)
    srv.register_problem("lat6", L=6, seed=0, impl="ref")
    g = ea3d(4, seed=0)
    srv.register_problem("g4", graph=g, coloring=lattice3d_coloring(4), K=2,
                         labels=slab_partition(4, 2), rng="lfsr")
    # unsupported engine/precision pair: clear error at submit, not a
    # failed job (let alone a packing shape error)
    with pytest.raises(ValueError, match="lattice/dsim_dist path"):
        srv.submit("g4", engine="dsim", precision="bitplane", sweeps=16)
    # the admission cap is the scheduler budget: min(per-call cap 64,
    # MAX_LANE_WORDS words); word-straddling counts (e.g. 40) are legal now
    with pytest.raises(ValueError, match=r"\[1, 64\]"):
        srv.submit("lat6", engine="lattice", precision="bitplane",
                   replicas=100, sweeps=16)
    a = srv.submit("lat6", engine="lattice", precision="bitplane",
                   replicas=4, sweeps=32, sync_every=4, seed=1)
    b = srv.submit("lat6", engine="lattice", precision="bitplane",
                   replicas=8, sweeps=32, sync_every=4, seed=2)
    ra, rb = srv.result(a), srv.result(b)
    assert ra["status"] == "done" and rb["status"] == "done"
    assert ra["packed_with"] == 1 and rb["packed_with"] == 1
    assert ra["energies"].shape[1] == 4 and rb["energies"].shape[1] == 8
    assert ra["best_energy"] < 0 and rb["best_energy"] < 0
    assert ra["flips"] > 0 and rb["flips"] > 0
    # a solo bitplane job of the same spec reproduces its packed lanes
    solo = srv.submit("lat6", engine="lattice", precision="bitplane",
                      replicas=4, sweeps=32, sync_every=4, seed=1)
    rs = srv.result(solo)
    np.testing.assert_array_equal(rs["energies"], ra["energies"])


def test_server_pool_keys_bitplane_by_word_count():
    """R=33 and R=64 submissions both clamp to the W=2 (64-lane) executed
    width, so they share ONE pooled executable: the second is a pool hit,
    never a recompile.  ``prewarm_words=2`` builds that same bucket at
    register time."""
    from repro.serve.server import SampleServer
    srv = SampleServer(pack=True, warm_compile=False)
    srv.register_problem("lat4", L=4, seed=0, impl="ref")
    a = srv.submit("lat4", engine="lattice", precision="bitplane",
                   replicas=33, sweeps=16, sync_every=4, seed=1)
    ra = srv.result(a)
    assert ra["status"] == "done" and ra["cold_start"] is True
    assert ra["energies"].shape[1] == 33         # own lanes only
    b = srv.submit("lat4", engine="lattice", precision="bitplane",
                   replicas=64, sweeps=16, sync_every=4, seed=2)
    rb = srv.result(b)
    assert rb["status"] == "done"
    assert rb["cold_start"] is False             # same W=2 pool key
    assert rb["energies"].shape[1] == 64
    # register-time prewarm of the W=2 bucket serves the first tenant warm
    srv2 = SampleServer(pack=True, warm_compile=False)
    srv2.register_problem("lat4", L=4, seed=0, impl="ref",
                          prewarm_bitplane=True, prewarm_words=2)
    srv2.prewarm_threads[0].join(timeout=400)
    assert not srv2.prewarm_threads[0].is_alive()
    c = srv2.submit("lat4", engine="lattice", precision="bitplane",
                    replicas=40, sweeps=16, sync_every=4, seed=3)
    rc = srv2.result(c)
    assert rc["status"] == "done" and rc["cold_start"] is False
    with pytest.raises(ValueError, match="prewarm_words"):
        srv2.register_problem("bad", L=4, prewarm_words=0)
