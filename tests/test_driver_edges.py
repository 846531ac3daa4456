"""Shared-driver edge cases: chunk planning, record-point quantization,
flip-cap bounds, and the resumable RecordedCursor surface."""

import pickle

import jax
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.annealing import constant_schedule, ea_schedule
from repro.core.coloring import lattice3d_coloring
from repro.core.graph import ea3d
from repro.engines import make_engine
from repro.core.lattice import build_ea3d_lattice
from repro.engines.base import (RecordedCursor, chunk_plan, flips_chunk_cap,
                                quantize_record_points, run_recorded_driver)
from repro.obs import Tracer, flip_syncs, install


# -- chunk_plan ----------------------------------------------------------------

def test_chunk_plan_point_zero_is_empty():
    assert chunk_plan([0]) == []
    assert chunk_plan([0, 0]) == []


def test_chunk_plan_duplicate_points():
    plan = chunk_plan([4, 4, 8])
    assert sum(plan) == 8
    acc, seen = 0, set()
    for c in plan:
        acc += c
        seen.add(acc)
    assert {4, 8} <= seen


def test_chunk_plan_unsorted_rejected():
    with pytest.raises(ValueError, match="nondecreasing"):
        chunk_plan([8, 4])


def test_chunk_plan_non_pow2_max_chunk_rejected():
    for bad in (3, 6, 0, -4):
        with pytest.raises(ValueError, match="power of two"):
            chunk_plan([4], max_chunk=bad)


def test_chunk_plan_max_chunk_one():
    assert chunk_plan([5], max_chunk=1) == [1] * 5


def test_chunk_plan_covers_every_point():
    pts = [1, 7, 8, 21, 64]
    plan = chunk_plan(pts, max_chunk=16)
    acc, seen = 0, set()
    for c in plan:
        assert c & (c - 1) == 0 and 1 <= c <= 16
        acc += c
        seen.add(acc)
    assert set(pts) <= seen


# -- quantize_record_points ----------------------------------------------------

def test_quantize_point_zero_snaps_to_S():
    assert quantize_record_points([0], S=4) == [4]


def test_quantize_S_larger_than_first_point():
    # S > p: every point clamps up to at least one exchange period
    assert quantize_record_points([2, 16], S=8) == [8, 16]
    assert quantize_record_points([1, 2, 3], S=8) == [8]


def test_quantize_duplicates_and_unsorted():
    assert quantize_record_points([8, 4, 4, 8], S=4) == [4, 8]
    assert quantize_record_points([9, 6, 6], S=4) == [8]


def test_quantize_clamps_rounding_past_limit():
    # round-to-nearest can overshoot the schedule (1000 -> 1001 with S=7);
    # with limit= the point clamps to the last reachable boundary
    assert quantize_record_points([1000], 7) == [1001]
    assert quantize_record_points([1000], 7, limit=1000) == [994]
    assert quantize_record_points([20], 7, limit=20) == [14]
    assert quantize_record_points([16], 4, limit=16) == [16]  # no-op in range


def test_driver_survives_awkward_sync_near_schedule_end():
    _, rec = run_recorded_driver(
        state={}, schedule=constant_schedule(1.0, 20), record_points=[20],
        chunk_fn=_noop_chunk, record_fn=lambda st: jnp.zeros(()),
        sync_every=7)
    assert list(rec.times) == [14]           # last reachable boundary


# -- flips_chunk_cap -----------------------------------------------------------

def test_flips_chunk_cap_bounds_and_pow2():
    for fps, spi in [(1, 1), (125, 4), (1 << 20, 1), (7, 3)]:
        cap = flips_chunk_cap(fps, spi)
        assert cap >= 1 and cap & (cap - 1) == 0
        assert cap * fps * spi < (1 << 31)


def test_flips_chunk_cap_degenerate_inputs():
    assert flips_chunk_cap(0) == 1 << 30          # clamped to >= 1 flip
    assert flips_chunk_cap(1, 0) == 1 << 30
    assert flips_chunk_cap(1 << 40) == 1          # never below one iter


# -- driver guards -------------------------------------------------------------

def _noop_chunk(state, betas2d, iters, S):
    return state


def test_driver_empty_record_points_rejected():
    with pytest.raises(ValueError, match="non-empty"):
        run_recorded_driver(
            state={}, schedule=constant_schedule(1.0, 8), record_points=[],
            chunk_fn=_noop_chunk, record_fn=lambda st: jnp.zeros(()))
    with pytest.raises(ValueError, match="non-empty"):
        RecordedCursor(
            state={}, schedule=constant_schedule(1.0, 8), record_points=[],
            chunk_fn=_noop_chunk, record_fn=lambda st: jnp.zeros(()))


def test_driver_schedule_too_short_rejected():
    with pytest.raises(ValueError, match="shorter"):
        run_recorded_driver(
            state={}, schedule=constant_schedule(1.0, 8), record_points=[16],
            chunk_fn=_noop_chunk, record_fn=lambda st: jnp.zeros(()))


def test_driver_quantizes_S_above_first_point():
    seen = []

    def chunk(state, betas2d, iters, S):
        seen.append((iters, S))
        return state

    _, rec = run_recorded_driver(
        state={}, schedule=constant_schedule(1.0, 32), record_points=[2],
        chunk_fn=chunk, record_fn=lambda st: jnp.zeros(()), sync_every=8)
    assert list(rec.times) == [8]            # 2 snapped up to one period
    assert all(S == 8 for _, S in seen)


# -- the resumable cursor ------------------------------------------------------

L = 4
SW = 32


@pytest.fixture(scope="module")
def gibbs_handle():
    g = ea3d(L, seed=5)
    return g, make_engine("gibbs", g, coloring=lattice3d_coloring(L),
                          rng="lfsr", replicas=2)


def test_cursor_matches_one_shot_bitwise(gibbs_handle):
    g, h = gibbs_handle
    sch = ea_schedule(SW)
    pts = [SW // 4, SW // 2, SW]
    st0 = h.init_state(seed=3)
    st1, rec1 = h.run_recorded(st0, sch, pts)
    cur = h.start_recorded(h.init_state(seed=3), sch, pts)
    steps = 0
    while not cur.done:
        assert cur.advance(1) == 1
        steps += 1
    assert cur.advance(1) == 0               # done cursors are inert
    rec2 = cur.record()
    assert steps >= len(pts)
    assert np.array_equal(np.asarray(rec1.energies),
                          np.asarray(rec2.energies))
    assert np.array_equal(rec1.times, rec2.times)
    assert rec1.flips == rec2.flips
    assert np.array_equal(np.asarray(h.global_spins(st1)),
                          np.asarray(h.global_spins(cur.state)))


def test_cursor_partial_records_stream(gibbs_handle):
    g, h = gibbs_handle
    pts = [8, 16, 24, 32]
    cur = h.start_recorded(h.init_state(seed=1), ea_schedule(SW), pts)
    seen_pts, seen_flips = [0], [0]
    while not cur.done:
        cur.advance(1)
        rec = cur.record()
        assert len(rec.times) >= seen_pts[-1]
        assert rec.flips >= seen_flips[-1]   # exact and monotone mid-run
        if len(rec.times):
            assert rec.energies.shape == (len(rec.times), 2)
        seen_pts.append(len(rec.times))
        seen_flips.append(rec.flips)
    assert cur.sweeps_done == cur.total_sweeps == SW
    assert seen_pts[-1] == len(pts)
    per_rep = cur.flips_per_replica()
    assert per_rep.shape == (2,) and int(per_rep.sum()) == cur.flips > 0


def test_cursor_warm_does_not_advance(gibbs_handle):
    g, h = gibbs_handle
    sch = ea_schedule(SW)
    cur = h.start_recorded(h.init_state(seed=2), sch, [SW])
    cur.warm()
    assert cur.sweeps_done == 0 and not cur.done
    cur.advance(1000)
    ref = h.start_recorded(h.init_state(seed=2), sch, [SW])
    ref.advance(1000)
    assert np.array_equal(np.asarray(cur.record().energies),
                          np.asarray(ref.record().energies))
    assert cur.record().flips == ref.record().flips


def test_cursor_empty_partial_record(gibbs_handle):
    g, h = gibbs_handle
    cur = h.start_recorded(h.init_state(seed=0), ea_schedule(SW), [SW])
    rec = cur.record()                       # before any advance
    assert len(rec.times) == 0 and rec.flips == 0


# -- device-side flip snapshots ------------------------------------------------

def _syncs() -> dict:
    fam = flip_syncs.flip_syncs().counter(flip_syncs.FLIP_SYNCS)
    return {k: fam.labels(kind=k).value for k in flip_syncs.KINDS}


def _moved(before: dict) -> dict:
    now = _syncs()
    return {k: now[k] - before[k] for k in flip_syncs.KINDS}


@pytest.fixture(scope="module")
def lattice_int8_handle():
    return make_engine("lattice", lattice=build_ea3d_lattice(8, seed=1),
                       precision="int8", replicas=2)


@pytest.mark.parametrize("record_each", [False, True],
                         ids=["advance_only", "record_each"])
@pytest.mark.parametrize("engine", ["gibbs", "lattice_int8"])
def test_cursor_record_every_period_matches_one_shot(engine, record_each,
                                                     request):
    """A record point at every exchange period: snapshots taken at each
    and settled late (or at each, when the caller records every time)
    give the one-shot driver's times, energies and flips bit for bit."""
    if engine == "gibbs":
        h, S = request.getfixturevalue("gibbs_handle")[1], 1
    else:
        h, S = request.getfixturevalue("lattice_int8_handle"), 4
    sch = ea_schedule(SW)
    pts = list(range(S, SW + 1, S))
    st1, rec1 = h.run_recorded(h.init_state(seed=3), sch, pts, sync_every=S)
    ref = h.start_recorded(h.init_state(seed=3), sch, pts, sync_every=S)
    ref.advance(len(pts))
    cur = h.start_recorded(h.init_state(seed=3), sch, pts, sync_every=S)
    while not cur.done:
        cur.advance(1)
        if record_each:
            cur.record()
    rec2 = cur.record()
    assert np.array_equal(rec1.times, rec2.times)
    assert np.array_equal(np.asarray(rec1.energies),
                          np.asarray(rec2.energies))
    assert rec1.flips == rec2.flips == cur.flips > 0
    per_rep = cur.flips_per_replica()
    assert np.array_equal(per_rep, ref.flips_per_replica())
    assert int(per_rep.sum()) == rec1.flips
    assert np.array_equal(np.asarray(h.global_spins(st1)),
                          np.asarray(h.global_spins(cur.state)))


@jax.jit
def _fake_step(st, betas2d):
    return {"flips": st["flips"] + jnp.int32(3 * betas2d.size),
            "E": st["E"] + jnp.sum(betas2d)}


def test_cursor_snapshots_without_host_reads():
    """64 record points advanced without a record(): 64 device-side
    snapshots, no settle, never more than two chunks unconfirmed; the
    record() after them settles all 64 in one read."""
    P = 64
    blocked = []
    tr = Tracer(block=blocked.append)
    prev = install(tr)
    try:
        before = _syncs()
        cur = RecordedCursor(
            state={"flips": jnp.zeros((2,), jnp.int32), "E": jnp.zeros(())},
            schedule=constant_schedule(1.0, P),
            record_points=list(range(1, P + 1)),
            chunk_fn=lambda st, b, iters, S: _fake_step(st, b),
            record_fn=lambda st: st["E"], flips_of=lambda st: st["flips"],
            flips_per_sweep=6)
        unconfirmed = []
        while not cur.done:
            cur.advance(1)
            unconfirmed.append(len(cur._inflight))
        assert _moved(before) == {"snapshot": P, "settle": 0, "wait": P - 2}
        assert max(unconfirmed) == 2
        rec = cur.record()
        assert _moved(before) == {"snapshot": P, "settle": 1, "wait": P - 2}
    finally:
        install(prev)
    assert rec.flips == 6 * P and list(cur.flips_vec) == [3 * P, 3 * P]
    assert list(rec.times) == list(range(1, P + 1))
    assert "cursor.wait" in {s["name"] for s in tr.spans()}
    assert blocked == []


def test_checkpoint_with_snapshots_pending_resumes_bitwise(gibbs_handle):
    g, h = gibbs_handle
    sch = ea_schedule(SW)
    pts = list(range(2, SW + 1, 2))
    ref = h.start_recorded(h.init_state(seed=6), sch, pts)
    ref.advance(len(pts))
    cur = h.start_recorded(h.init_state(seed=6), sch, pts)
    before = _syncs()
    cur.advance(5)
    assert _moved(before)["settle"] == 0     # five snapshots pending
    ck = pickle.loads(pickle.dumps(cur.checkpoint()))
    assert _moved(before)["settle"] == 1
    fresh = h.start_recorded(h.init_state(seed=0), sch, pts)
    fresh.restore_checkpoint(ck)
    fresh.advance(len(pts))
    rec, want = fresh.record(), ref.record()
    assert np.array_equal(rec.times, want.times)
    assert np.array_equal(np.asarray(rec.energies),
                          np.asarray(want.energies))
    assert rec.flips == want.flips
    assert np.array_equal(fresh.flips_per_replica(), ref.flips_per_replica())
    assert np.array_equal(np.asarray(h.global_spins(fresh.state)),
                          np.asarray(h.global_spins(ref.state)))


# -- snapshot / restore --------------------------------------------------------

def test_snapshot_pickles_and_resumes_bitwise(gibbs_handle):
    import pickle
    from repro.core.snapshot import snapshot_nbytes
    g, h = gibbs_handle
    sch = ea_schedule(SW)
    st = h.init_state(seed=4)
    st, _ = h.run_recorded(st, sch, [SW])    # mid-trajectory state
    snap = h.snapshot(st)
    assert snapshot_nbytes(snap) > 0
    restored = h.restore(pickle.loads(pickle.dumps(snap)))
    a, ra = h.run_recorded(st, sch, [SW])
    b, rb = h.run_recorded(restored, sch, [SW])
    assert np.array_equal(np.asarray(ra.energies), np.asarray(rb.energies))
    assert ra.flips == rb.flips
    assert np.array_equal(np.asarray(h.global_spins(a)),
                          np.asarray(h.global_spins(b)))


def test_snapshot_restore_lattice_resharded():
    import pickle
    hl = make_engine("lattice", L=4, seed=2, replicas=2)
    sch = ea_schedule(16)
    st = hl.init_state(seed=0)
    st, _ = hl.run_recorded(st, sch, [16], sync_every=4)
    restored = hl.restore(pickle.loads(pickle.dumps(hl.snapshot(st))))
    a, _ = hl.run_recorded(st, sch, [16], sync_every=4)
    b, _ = hl.run_recorded(restored, sch, [16], sync_every=4)
    assert np.array_equal(np.asarray(a.m), np.asarray(b.m))
    assert np.array_equal(np.asarray(a.s), np.asarray(b.s))
