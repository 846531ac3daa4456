"""Unified engine layer: registry round-trip, replica axis, fused kernel,
shared recording driver, exact flip accounting."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.engines import (Engine, RunRecord, chunk_plan, make_engine,
                           run_recorded_driver, spawn_seeds)
from repro.engines.base import flips_chunk_cap
from repro.core.graph import ea3d
from repro.core.coloring import lattice3d_coloring
from repro.core.partition import slab_partition
from repro.core.gibbs import GibbsEngine
from repro.core.dsim import build_partitioned, DSIMEngine
from repro.core.annealing import (ea_schedule, constant_schedule,
                                  replica_beta_arrays)

L = 6
SW = 48


@pytest.fixture(scope="module")
def setup():
    g = ea3d(L, seed=7)
    col = lattice3d_coloring(L)
    labels = slab_partition(L, 2)
    return g, col, labels


def _mk(name, setup, replicas=1, **kw):
    g, col, labels = setup
    if name == "gibbs":
        return make_engine("gibbs", g, coloring=col, rng="lfsr",
                           replicas=replicas, **kw)
    if name == "dsim":
        return make_engine("dsim", g, coloring=col, K=2, labels=labels,
                           rng="lfsr", replicas=replicas, **kw)
    if name == "dsim_dist":
        # K=1 runs the full shard_map path on the single test device
        return make_engine("dsim_dist", g, coloring=col, K=1,
                           labels=np.zeros(g.n, np.int32), rng="lfsr",
                           replicas=replicas, **kw)
    return make_engine("lattice", L=L, seed=7, replicas=replicas, **kw)


# -- registry round-trip ------------------------------------------------------

@pytest.mark.parametrize("name", ["gibbs", "dsim", "dsim_dist", "lattice"])
def test_registry_round_trip(name, setup):
    g, col, labels = setup
    h = _mk(name, setup, replicas=2)
    assert isinstance(h, Engine)
    assert h.replicas == 2 and h.n_sites == g.n
    st = h.init_state(seed=0)
    st, rec = h.run_recorded(st, ea_schedule(SW), [SW // 2, SW],
                             sync_every=4)
    assert isinstance(rec, RunRecord)
    assert rec.energies.shape == (2, 2)            # (points, R)
    assert rec.flips > 0
    e = np.asarray(h.energy(st))
    assert e.shape == (2,)
    np.testing.assert_allclose(e, np.asarray(rec.energies[-1]), atol=1e-3)
    spins = np.asarray(h.global_spins(st))
    assert spins.shape == (2, g.n)
    assert set(np.unique(spins)) <= {-1, 1}
    # annealing actually anneals
    assert float(rec.energies[-1].min()) < 0


def test_unknown_engine_rejected(setup):
    with pytest.raises(ValueError):
        make_engine("does-not-exist")


# -- replica axis -------------------------------------------------------------

@pytest.mark.parametrize("name", ["gibbs", "dsim"])
def test_replica_r1_matches_legacy_bitwise(name, setup):
    """The registry at R=1 reproduces the legacy class exactly."""
    g, col, labels = setup
    h = _mk(name, setup, replicas=1)
    if name == "gibbs":
        legacy = GibbsEngine(g, col, rng="lfsr")
    else:
        legacy = DSIMEngine(build_partitioned(g, col, labels, 2), rng="lfsr")
    sh = h.init_state(seed=3)
    sl = legacy.init_state(seed=3)
    sh, rec = h.run_recorded(sh, ea_schedule(SW), [SW], sync_every=4)
    if name == "gibbs":
        sl, _ = legacy.run_recorded(sl, ea_schedule(SW), [SW])
        ml = np.asarray(sl.m)
    else:
        sl, _ = legacy.run_recorded(sl, ea_schedule(SW), [SW], sync_every=4)
        ml = np.asarray(legacy.global_spins(sl))
    mh = np.asarray(h.global_spins(sh))[0]
    assert (mh == ml).all()


def test_lattice_r1_matches_direct_engine(setup):
    from repro.core.lattice import build_ea3d_lattice
    from repro.core.lattice_dsim import LatticeDSIM
    from repro.compat import make_mesh, auto_axes
    h = _mk("lattice", setup, replicas=1)
    prob = build_ea3d_lattice(L, seed=7)
    mesh = make_mesh((1,), ("data",), axis_types=auto_axes(1))
    direct = LatticeDSIM(prob, mesh, dim_axes=("data", None, None))
    sh, sd = h.init_state(seed=3), direct.init_state(seed=3)
    sh, _ = h.run_recorded(sh, ea_schedule(SW), [SW], sync_every=4)
    sd, _ = direct.run_recorded(sd, ea_schedule(SW), [SW], sync_every=4)
    assert (np.asarray(sh.m) == np.asarray(sd.m)).all()
    assert (np.asarray(sh.s) == np.asarray(sd.s)).all()


@pytest.mark.parametrize("name", ["gibbs", "dsim", "dsim_dist", "lattice"])
def test_replicas_mutually_independent(name, setup):
    """R=4 chains diverge: pairwise-distinct spins and decorrelated signs."""
    h = _mk(name, setup, replicas=4)
    st = h.init_state(seed=0)
    st, rec = h.run_recorded(st, constant_schedule(0.8, SW), [SW],
                             sync_every=4)
    spins = np.asarray(h.global_spins(st)).astype(np.float64)
    n = spins.shape[1]
    for a in range(4):
        for b in range(a + 1, 4):
            assert (spins[a] != spins[b]).any()
            # at beta below the glass transition, independent chains show
            # only weak overlap: |q_ab| far from 1
            q = abs(float((spins[a] * spins[b]).mean()))
            assert q < 0.6, (a, b, q)


def test_replica_prefix_stability(setup):
    """Replica r of an R=2 batch equals replica r of an R=4 batch (seed
    spawning is prefix-stable), so growing the batch never reshuffles."""
    g, col, labels = setup
    h2 = _mk("gibbs", setup, replicas=2)
    h4 = _mk("gibbs", setup, replicas=4)
    s2, s4 = h2.init_state(seed=9), h4.init_state(seed=9)
    s2, _ = h2.run_recorded(s2, ea_schedule(SW), [SW])
    s4, _ = h4.run_recorded(s4, ea_schedule(SW), [SW])
    m2 = np.asarray(h2.global_spins(s2))
    m4 = np.asarray(h4.global_spins(s4))
    assert (m2 == m4[:2]).all()


def test_per_replica_beta_arrays(setup):
    g, col, labels = setup
    sch = ea_schedule(SW)
    bR = replica_beta_arrays(sch, 3, spread=0.2)
    assert bR.shape == (SW, 3)
    assert (bR[:, 0] < bR[:, 2]).all()
    eng = GibbsEngine(g, col, rng="lfsr")
    st = eng.init_state(seed=0, replicas=3)
    st, rec = eng.run_recorded_full(st, sch, [SW], betas_R=bR)
    assert rec.energies.shape == (1, 3)
    # identical spread=0 arrays reproduce the shared-schedule run bitwise
    st1 = eng.init_state(seed=0, replicas=3)
    st1, rec1 = eng.run_recorded_full(st1, sch, [SW])
    st2 = eng.init_state(seed=0, replicas=3)
    st2, rec2 = eng.run_recorded_full(st2, sch, [SW],
                                      betas_R=replica_beta_arrays(sch, 3))
    assert (np.asarray(st1.m) == np.asarray(st2.m)).all()


# -- fused multi-phase kernel -------------------------------------------------

@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_fused_sweep_matches_per_phase_bitwise(impl):
    """Acceptance: fused kernel == per-phase reference on an (8,8,8) brick,
    bitwise, through the full engine (halo exchange included)."""
    hf = make_engine("lattice", L=8, seed=5, replicas=2, fused=True,
                     impl=impl)
    hp = make_engine("lattice", L=8, seed=5, replicas=2, fused=False,
                     impl=impl)
    sf, sp = hf.init_state(seed=0), hp.init_state(seed=0)
    sf, rf = hf.run_recorded(sf, ea_schedule(16), [16], sync_every=4)
    sp, rp = hp.run_recorded(sp, ea_schedule(16), [16], sync_every=4)
    assert (np.asarray(sf.m) == np.asarray(sp.m)).all()
    assert (np.asarray(sf.s) == np.asarray(sp.s)).all()
    assert rf.flips == rp.flips > 0


def test_fused_kernel_op_level_bitwise():
    from repro.kernels.ops import pbit_update_op, pbit_sweep_op
    rng = np.random.default_rng(0)
    shape = (8, 8, 8)
    m = jnp.asarray(rng.choice([-1, 1], size=shape).astype(np.int8))
    s = jnp.asarray(rng.integers(1, 2 ** 32, size=shape, dtype=np.uint32))
    h = jnp.asarray(rng.normal(0, 0.1, shape).astype(np.float32))
    w6 = tuple(jnp.asarray(rng.choice([-1.0, 0.0, 1.0], size=shape)
                           .astype(np.float32)) for _ in range(6))
    halos = tuple(jnp.asarray(rng.choice([-1, 1], sh).astype(np.int8))
                  for sh in [(8, 8)] * 6)
    par = ((np.indices(shape).sum(axis=0)) % 2).astype(np.int8)
    masks = jnp.asarray(np.stack([par, 1 - par]))
    betas = jnp.asarray([1.0, 2.0, 3.0], jnp.float32)
    mm, ss, fl = m, s, 0
    for t in range(3):
        for c in range(2):
            m2, ss = pbit_update_op(mm, ss, betas[t], masks[c], h, w6, halos,
                                    impl="interpret")
            fl += int((np.asarray(m2) != np.asarray(mm)).sum())
            mm = m2
    mf, sf, flf = pbit_sweep_op(m, s, betas, masks, h, w6, halos,
                                impl="interpret")
    assert (np.asarray(mf) == np.asarray(mm)).all()
    assert (np.asarray(sf) == np.asarray(ss)).all()
    assert int(flf) == fl


# -- shared driver / exact flip accounting ------------------------------------

def test_chunk_plan_max_chunk():
    plan = chunk_plan([5, 9, 64], max_chunk=8)
    acc, seen = 0, []
    for c in plan:
        assert c & (c - 1) == 0 and c <= 8
        acc += c
        seen.append(acc)
    for p in (5, 9, 64):
        assert p in seen
    with pytest.raises(ValueError):
        chunk_plan([4], max_chunk=3)


@pytest.mark.parametrize("every_chunk", [False, True],
                         ids=["one_point", "every_chunk"])
def test_flip_total_exact_beyond_int32(every_chunk):
    """>2**31 flips accumulate exactly: the device counter is a wrapping
    int32 odometer, the driver's host-side total is an exact Python int.
    With a record point at every chunk, 512 snapshots spanning two wraps
    are settled in one read."""
    from repro.obs import flip_syncs
    FLIPS_PER_SWEEP = 1 << 24
    TOTAL = 512                                   # 512 * 2^24 = 2^33 flips
    cap = flips_chunk_cap(FLIPS_PER_SWEEP, 1)
    assert cap * FLIPS_PER_SWEEP < (1 << 31)      # per-chunk delta unambiguous

    class FakeState(dict):
        pass

    def chunk_fn(state, betas2d, iters, S):
        d = int(betas2d.shape[0]) * int(betas2d.shape[1]) * FLIPS_PER_SWEEP
        # int32 odometer semantics: wraps mod 2^32 (stored as uint32 here —
        # newer numpy refuses out-of-range int32 construction)
        wrapped = np.uint32((int(state["flips"]) + d) & 0xFFFFFFFF)
        return FakeState(flips=wrapped, E=state["E"])

    syncs = flip_syncs.flip_syncs().counter(flip_syncs.FLIP_SYNCS)
    settles = syncs.labels(kind="settle").value
    state = FakeState(flips=np.uint32(0), E=jnp.zeros(()))
    state, rec = run_recorded_driver(
        state=state, schedule=constant_schedule(1.0, TOTAL),
        record_points=list(range(1, TOTAL + 1)) if every_chunk else [TOTAL],
        chunk_fn=chunk_fn,
        record_fn=lambda st: st["E"], sync_every=1,
        flips_of=lambda st: st["flips"],
        flips_per_sweep=FLIPS_PER_SWEEP)
    exact = TOTAL * FLIPS_PER_SWEEP
    assert exact > (1 << 31)
    assert rec.flips == exact                      # wrapped twice, still exact
    assert syncs.labels(kind="settle").value == settles + 1


def test_engine_flip_totals_consistent(setup):
    """Engine-reported exact totals equal the device odometer when small."""
    h = _mk("gibbs", setup, replicas=1)
    st = h.init_state(seed=0)
    st, rec = h.run_recorded(st, ea_schedule(SW), [SW])
    assert rec.flips == int(np.uint32(np.asarray(st.flips)))


def test_spawn_seeds_distinct_and_stable():
    a = spawn_seeds(0, 8)
    b = spawn_seeds(0, 4)
    assert a[:4] == b
    assert len(set(a)) == 8
    assert spawn_seeds(1, 4) != spawn_seeds(0, 4)


# -- serve path ---------------------------------------------------------------

def test_sample_service_round_trip(setup):
    from repro.serve.sample_service import SampleService
    g, col, labels = setup
    svc = SampleService(graph=g, coloring=col, rng="lfsr")
    out = svc.submit(engine="gibbs", sweeps=SW, replicas=3, seed=1)
    assert out["energies"].shape == (1, 3)
    assert out["best_spins"].shape == (g.n,)
    assert out["best_energy"] == float(out["energies"][-1].min())
    assert out["flips"] > 0 and out["wall_s"] > 0
    # second submit reuses the cached handle
    out2 = svc.submit(engine="gibbs", sweeps=SW, replicas=3, seed=1)
    assert out2["best_energy"] == out["best_energy"]
