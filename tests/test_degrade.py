"""Degraded-mode mesh: boundary integrity, stale-hold failover, resync.

In-process tests cover the policy vocabulary, the wire checksum, the
failure-classification and fault-code plumbing, the checkpoint-spool hash
verification, the EtaMeter staleness accounting, the exchange-closure
cache invalidation, and the serve-layer wiring on a K=1 mesh.  The REAL
multi-device acceptance tests (poisoned exchanges on a 2-device mesh,
zero-corrupt-ghost ingestion, bitwise resync) run in SUBPROCESSES with a
forced host device count, like tests/test_dist.py.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core.degrade import (DegradePolicy, MeshHealthMonitor,
                                StateCorruption, health_init, wire_checksum)
from repro.serve.faults import FaultPlan, FaultRule, classify_error

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, devices: int = 2, timeout: int = 420) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=timeout)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


# -- policy vocabulary ---------------------------------------------------------

def test_degrade_policy_parse():
    assert DegradePolicy.parse(None) is None
    p = DegradePolicy.parse("stale_hold:4")
    assert p.mode == "stale_hold" and p.max_staleness == 4
    assert DegradePolicy.parse("stale_hold").mode == "stale_hold"
    assert DegradePolicy.parse("fail_fast").mode == "fail_fast"
    assert DegradePolicy.parse("freeze_boundary").mode == "freeze_boundary"
    assert DegradePolicy.parse(p) is p          # idempotent on instances
    with pytest.raises(ValueError):
        DegradePolicy.parse("best_effort")
    with pytest.raises(ValueError):
        DegradePolicy.parse("stale_hold:nope")
    with pytest.raises(ValueError):
        DegradePolicy(mode="gibberish")


def test_health_monitor_report_shape():
    mon = MeshHealthMonitor(DegradePolicy.parse("stale_hold:8"), 6,
                            kind="faces")
    rep = mon.report()
    for k in ("policy", "detections", "stale_exchanges", "exchanges_total",
              "max_staleness_seen", "delivered_fraction", "resyncs",
              "suspect", "sources", "staleness"):
        assert k in rep, k
    assert rep["detections"] == 0 and rep["delivered_fraction"] == 1.0
    assert not mon.suspect


# -- wire checksum -------------------------------------------------------------

def test_wire_checksum_detects_damage_and_reorder():
    a = np.arange(64, dtype=np.int8) - 32
    ck = int(wire_checksum(a))
    flipped = a.copy()
    flipped[17] ^= 2                       # one bit plane of one site
    assert int(wire_checksum(flipped)) != ck
    # position-weighted: a permutation of the same bytes must not collide
    perm = a.copy()
    perm[0], perm[1] = a[1], a[0]
    assert int(wire_checksum(perm)) != ck
    # dtype-specific paths agree with themselves deterministically
    w = np.arange(16, dtype=np.uint32)
    assert int(wire_checksum(w)) == int(wire_checksum(w.copy()))
    f = np.linspace(-1.0, 1.0, 16).astype(np.float32)
    fz = f.copy()
    fz[3] = np.nextafter(f[3], 2.0, dtype=np.float32)
    assert int(wire_checksum(f)) != int(wire_checksum(fz))


# -- failure classification ----------------------------------------------------

def _fake_xla_error(msg):
    cls = type("XlaRuntimeError", (RuntimeError,), {})
    return cls(msg)


def test_classify_error_jax_runtime():
    assert classify_error(_fake_xla_error(
        "RESOURCE_EXHAUSTED: out of memory allocating")) == "transient"
    assert classify_error(_fake_xla_error(
        "INTERNAL: cross-replica all-gather failed")) == "transient"
    assert classify_error(_fake_xla_error(
        "INVALID_ARGUMENT: shapes do not match")) == "permanent"
    # the duck-typed check wins over the generic tuples: a subclass of
    # ValueError named XlaRuntimeError still splits on the status code
    cls = type("XlaRuntimeError", (ValueError,), {})
    assert classify_error(cls("RESOURCE_EXHAUSTED: oom")) == "transient"


def test_classify_error_taxonomy_unchanged():
    assert classify_error(StateCorruption("mesh")) == "transient"
    assert classify_error(ValueError("bad")) == "permanent"
    assert classify_error(TimeoutError("slow")) == "transient"
    assert classify_error(RuntimeError("????")) == "transient"


# -- fault-code compilation ----------------------------------------------------

def test_exchange_codes_compile_and_replay():
    plan = FaultPlan([FaultRule(site="exchange_drop", rate=0.5)], seed=9)
    codes = plan.exchange_codes(64)
    assert codes is not None and codes.dtype == np.int32
    assert set(np.unique(codes)) <= {0, 1}
    assert 0 < int((codes == 1).sum()) < 64
    # deterministic: replay() and a second compile agree bitwise
    np.testing.assert_array_equal(codes, plan.replay().exchange_codes(64))
    np.testing.assert_array_equal(codes, plan.exchange_codes(64))


def test_exchange_codes_index_after_and_overlap():
    plan = FaultPlan([FaultRule(site="exchange_drop", index=3),
                      FaultRule(site="exchange_corrupt", index=3),
                      FaultRule(site="exchange_drop", after=8)], seed=0)
    codes = plan.exchange_codes(12)
    assert codes[3] == 2                  # corrupt wins the overlap
    assert (codes[8:] == 1).all() and (codes[:3] == 0).all()
    # no engine-site rules -> None (host-site rules don't leak in)
    assert FaultPlan([FaultRule(site="chunk")]).exchange_codes(8) is None


def test_engine_rejects_codes_without_policy():
    from repro.compat import auto_axes, make_mesh
    from repro.core.coloring import lattice3d_coloring
    from repro.core.dsim import build_partitioned
    from repro.core.dsim_dist import DistDSIMEngine
    from repro.core.graph import ea3d

    g = ea3d(4, seed=1)
    prob = build_partitioned(g, lattice3d_coloring(4),
                             np.zeros(g.n, np.int32), 1)
    mesh = make_mesh((1,), ("data",), axis_types=auto_axes(1))
    e = DistDSIMEngine(prob, mesh, rng="lfsr", precision="int8")
    with pytest.raises(ValueError, match="degrade"):
        e.set_exchange_faults(np.zeros(4, np.int32))


# -- checkpoint-spool content verification ------------------------------------

def test_spool_rejects_bit_flipped_checkpoint(tmp_path):
    from repro.serve.spool import CheckpointSpool

    spool = CheckpointSpool(str(tmp_path))
    digest = spool.put({"token": ("batch", "job-1"), "sweeps_done": 128})
    path = os.path.join(str(tmp_path), digest + ".ck")
    blob = bytearray(open(path, "rb").read())
    blob[len(blob) // 2] ^= 0x40                  # one flipped bit
    open(path, "wb").write(bytes(blob))
    with pytest.raises(FileNotFoundError, match="content-hash"):
        spool.load(digest)
    assert spool.corrupt_checkpoints == 1
    assert not os.path.exists(path)               # treated as missing
    assert spool.stats()["corrupt_checkpoints"] == 1
    # records() scan skips (and clears) corruption instead of raising
    d2 = spool.put({"token": ("batch", "job-2"), "sweeps_done": 64})
    p2 = os.path.join(str(tmp_path), d2 + ".ck")
    open(p2, "ab").write(b"\x00tail")             # appended garbage
    assert spool.records() == []
    assert spool.corrupt_checkpoints == 2


def test_spool_truncated_checkpoint(tmp_path):
    from repro.serve.spool import CheckpointSpool

    spool = CheckpointSpool(str(tmp_path))
    digest = spool.put({"token": ("batch", "job-1"), "sweeps_done": 7})
    path = os.path.join(str(tmp_path), digest + ".ck")
    blob = open(path, "rb").read()
    open(path, "wb").write(blob[:len(blob) // 2])
    with pytest.raises(FileNotFoundError):
        spool.load(digest)
    assert spool.corrupt_checkpoints == 1


# -- EtaMeter degraded accounting ---------------------------------------------

def test_eta_meter_effective_eta_accounting():
    from repro.obs import EtaMeter

    m = EtaMeter(n_color=1, c_max=0.045, sync_every=10)
    m.record_chunk(100, 1.0, exchanges=10)
    m.record_exchange(0.5, 10)            # t_ex = 0.05 s
    # t_pbit = (1.0 - 10 * 0.05) / 100 = 0.005 -> eta = 0.1
    assert m.eta == pytest.approx(0.1)
    assert m.effective_eta == pytest.approx(0.1)      # healthy: equal
    rep = m.report()
    assert rep["margin"] > 1.0 and rep["degraded_below_threshold"] is False
    m.note_stale(3, 10, max_staleness=2)
    assert m.stale_exchanges == 3
    assert m.max_staleness_seen == 2
    assert m.delivered_fraction == pytest.approx(0.7)
    assert m.effective_eta == pytest.approx(0.07)
    rep = m.report()
    # threshold 2 * 1 * 0.045 = 0.09: clean margin >= 1, effective below
    assert rep["effective_eta"] < rep["eta_threshold"] <= rep["measured_eta"]
    assert rep["degraded_below_threshold"] is True
    assert rep["stale_exchanges"] == 3
    assert rep["max_staleness_seen"] == 2


def test_health_carry_roundtrip():
    carry = health_init(6)
    assert len(carry) == 6
    assert carry[1].shape == (6,)
    mon = MeshHealthMonitor(DegradePolicy.parse("stale_hold:2"), 6,
                            kind="faces")
    # a carry whose max staleness exceeds the budget escalates
    bad = (np.uint32(4), np.full(6, 3, np.int32), np.int32(0),
           np.int32(3), np.int32(3), np.int32(3))
    with pytest.raises(StateCorruption, match="staleness"):
        mon.update(bad, exchanges=4)
    # fail_fast escalates on the first detection
    mon2 = MeshHealthMonitor(DegradePolicy.parse("fail_fast"), 6,
                             kind="faces")
    det = (np.uint32(1), np.zeros(6, np.int32), np.int32(0),
           np.int32(1), np.int32(0), np.int32(0))
    with pytest.raises(StateCorruption, match="fail_fast"):
        mon2.update(det, exchanges=1)


# -- exchange-closure cache invalidation --------------------------------------

def test_boundary_exchange_fn_cache_invalidated_on_restore():
    from repro.compat import auto_axes, make_mesh
    from repro.core.coloring import lattice3d_coloring
    from repro.core.graph import ea3d
    from repro.engines import make_engine

    g = ea3d(4, seed=2)
    mesh = make_mesh((1,), ("data",), axis_types=auto_axes(1))
    h = make_engine("dsim_dist", g, coloring=lattice3d_coloring(4), K=1,
                    labels=np.zeros(g.n, np.int32), mesh=mesh, rng="lfsr",
                    precision="int8", replicas=2)
    st = h.init_state(seed=5)
    fn1 = h.eng.boundary_exchange_fn()
    assert h.eng.boundary_exchange_fn() is fn1    # cached while valid
    snap = h.snapshot(st)
    st2 = h.restore(snap)                         # re-shards -> invalidate
    assert h.eng._exchange_only_fn is None
    fn2 = h.eng.boundary_exchange_fn()
    assert fn2 is not fn1
    # the rebuilt closure runs against the restored (re-sharded) state
    ghosts = fn2(st2)
    np.testing.assert_array_equal(np.asarray(ghosts),
                                  np.asarray(fn2(st2)))


def test_lattice_exchange_fn_cache_invalidated_on_restore():
    from repro.engines import make_engine

    h = make_engine("lattice", L=4, seed=3, impl="ref", precision="int8",
                    replicas=2)
    st = h.init_state(seed=5)
    fn1 = h.eng.boundary_exchange_fn()
    st2 = h.restore(h.snapshot(st))
    assert h.eng._exchange_only_fn is None
    fn2 = h.eng.boundary_exchange_fn()
    assert fn2 is not fn1
    halos = fn2(st2)
    assert len(halos) == 6


# -- serve-layer wiring (K=1 mesh; no forced device count needed) -------------

def _graph_server(**kw):
    from repro.compat import auto_axes, make_mesh
    from repro.core.coloring import lattice3d_coloring
    from repro.core.graph import ea3d
    from repro.serve.server import SampleServer

    g = ea3d(4, seed=11)
    srv = SampleServer(warm_compile=False, retry_backoff_s=0.0, **kw)
    srv.register_problem("ea4", graph=g,
                         coloring=lattice3d_coloring(4), K=1,
                         labels=np.zeros(g.n, np.int32),
                         mesh=make_mesh((1,), ("data",),
                                        axis_types=auto_axes(1)),
                         rng="lfsr")
    return srv


def test_submit_degrade_policy_validation():
    srv = _graph_server()
    with pytest.raises(ValueError, match="mesh engines"):
        srv.submit("ea4", engine="gibbs", degrade_policy="stale_hold")
    with pytest.raises(ValueError, match="integer sync_every"):
        srv.submit("ea4", engine="dsim_dist", degrade_policy="stale_hold",
                   sync_every="phase")
    with pytest.raises(ValueError, match="degrade"):
        srv.submit("ea4", engine="dsim_dist", degrade_policy="best_effort",
                   sync_every=4)


def test_serve_degrade_provenance_clean():
    srv = _graph_server()
    jid = srv.submit("ea4", engine="dsim_dist", precision="int8", sweeps=32,
                     sync_every=4, seed=3, degrade_policy="stale_hold:8")
    out = srv.drain().result(jid)
    assert out["status"] == "done"
    deg = out["degrade"]
    assert deg is not None
    assert deg["policy"] == "stale_hold:8"
    assert deg["detections"] == 0
    assert deg["delivered_fraction"] == 1.0
    assert not deg["suspect"]
    st = srv.stats()
    assert st["exchange_integrity_failures"] == 0
    assert st["stale_exchanges"] == 0
    # a policy-free job on the same problem carries no provenance (and
    # compiles under a DIFFERENT pool key — the clean executable)
    jid2 = srv.submit("ea4", engine="dsim_dist", precision="int8",
                      sweeps=32, sync_every=4, seed=3)
    out2 = srv.drain().result(jid2)
    assert out2["status"] == "done" and out2["degrade"] is None
    assert srv.stats()["pool"]["size"] == 2


def test_serve_degrade_provenance_with_injected_drops():
    # poison the LAST of the 8 exchanges (sweeps=32, sync_every=4), so
    # the quarantine mark is still up when the batch retires — staleness
    # is *consecutive*, so a mid-run drop heals by run end
    plan = FaultPlan([FaultRule(site="exchange_drop", index=7)], seed=4)
    srv = _graph_server(fault_plan=plan)
    jid = srv.submit("ea4", engine="dsim_dist", precision="int8", sweeps=32,
                     sync_every=4, seed=3, degrade_policy="stale_hold:8")
    out = srv.drain().result(jid)
    assert out["status"] == "done"
    deg = out["degrade"]
    assert deg["detections"] == 1
    assert deg["stale_exchanges"] == 1
    assert deg["max_staleness_seen"] == 1
    assert deg["suspect"]
    assert 0.0 < deg["delivered_fraction"] < 1.0
    st = srv.stats()
    assert st["exchange_integrity_failures"] == 1
    assert st["stale_exchanges"] == 1


def test_serve_fail_fast_fails_job():
    plan = FaultPlan([FaultRule(site="exchange_corrupt", index=1)], seed=4)
    srv = _graph_server(fault_plan=plan, max_retries=0)
    jid = srv.submit("ea4", engine="dsim_dist", precision="int8", sweeps=32,
                     sync_every=4, seed=3, degrade_policy="fail_fast")
    out = srv.drain().result(jid)
    assert out["status"] == "failed"
    assert "StateCorruption" in out["error"]
    assert srv.stats()["exchange_integrity_failures"] >= 1


# -- 2-device acceptance (subprocess, forced host device count) ---------------

def test_degrade_zero_fault_parity_2dev():
    """stale_hold with ZERO injected faults is bitwise the normal run —
    both mesh engines, int8 and bitplane, on a real 2-device mesh."""
    run_py("""
        import numpy as np
        import jax
        assert jax.device_count() == 2
        from repro.core.graph import ea3d
        from repro.core.coloring import lattice3d_coloring
        from repro.core.partition import slab_partition
        from repro.core.dsim import build_partitioned
        from repro.core.dsim_dist import DistDSIMEngine
        from repro.core.lattice import build_ea3d_lattice
        from repro.core.lattice_dsim import LatticeDSIM
        from repro.core.annealing import ea_schedule
        from repro.compat import make_mesh, auto_axes

        L = 4
        sch = ea_schedule(40)
        mesh = make_mesh((2,), ("data",), axis_types=auto_axes(1))
        g = ea3d(L, seed=7)
        dprob = build_partitioned(g, lattice3d_coloring(L),
                                  slab_partition(L, 2), 2)
        lprob = build_ea3d_lattice(L, seed=7)

        def dist(prec, degrade):
            e = DistDSIMEngine(dprob, mesh, rng="lfsr", precision=prec,
                               replicas=3, degrade=degrade)
            st = e.init_state(seed=3)
            st, (_, E) = e.run_recorded(st, sch, [40], sync_every=4)
            return e, np.asarray(e.global_spins(st)), np.asarray(E)

        def lat(prec, degrade):
            e = LatticeDSIM(lprob, mesh, dim_axes=("data", None, None),
                            impl="ref", replicas=3, precision=prec,
                            degrade=degrade)
            st = e.init_state(seed=3)
            st, (_, E) = e.run_recorded(st, sch, [40], sync_every=4)
            return e, np.asarray(e.global_spins(st)), np.asarray(E)

        for mk in (dist, lat):
            for prec in ("int8", "bitplane"):
                eb, mb, Eb = mk(prec, None)
                ed, md, Ed = mk(prec, "stale_hold:4")
                np.testing.assert_array_equal(mb, md)
                np.testing.assert_array_equal(Eb, Ed)
                rep = ed.health.report()
                assert rep["detections"] == 0, rep
                assert rep["stale_exchanges"] == 0, rep
                assert rep["delivered_fraction"] == 1.0, rep
                assert rep["exchanges_total"] == 10, rep
        print("zero-fault parity ok")
        """)


def test_dsim_dist_poisoned_exchange_2dev():
    """Acceptance: 2-device mesh, corrupted exchange at the engine site.
    stale_hold completes with ZERO corrupted ghosts ingested (the corrupt
    arm is bitwise the drop arm), resync() returns ghosts bitwise equal
    to the no-fault trajectory, fail_fast raises StateCorruption, and
    freeze_boundary holds every source after first detection."""
    run_py("""
        import numpy as np
        import jax
        assert jax.device_count() == 2
        from repro.core.graph import ea3d
        from repro.core.coloring import lattice3d_coloring
        from repro.core.partition import slab_partition
        from repro.core.dsim import build_partitioned
        from repro.core.dsim_dist import DistDSIMEngine
        from repro.core.degrade import StateCorruption
        from repro.core.annealing import ea_schedule
        from repro.compat import make_mesh, auto_axes

        L = 4
        g = ea3d(L, seed=7)
        prob = build_partitioned(g, lattice3d_coloring(L),
                                 slab_partition(L, 2), 2)
        mesh = make_mesh((2,), ("data",), axis_types=auto_axes(1))
        sch = ea_schedule(40)   # 40 sweeps, sync 4 -> 10 exchanges

        def run(prec, degrade=None, codes=None):
            e = DistDSIMEngine(prob, mesh, rng="lfsr", precision=prec,
                               replicas=3, degrade=degrade)
            st = e.init_state(seed=3)
            if codes is not None:
                e.set_exchange_faults(codes)
            st, (_, E) = e.run_recorded(st, sch, [40], sync_every=4)
            return e, st, np.asarray(E)

        for prec in ("int8", "bitplane"):
            eb, sb, Eb = run(prec)                  # clean reference
            codes = np.zeros(10, np.int32); codes[-1] = 2
            ed, sd, Ed = run(prec, "stale_hold:4", codes)
            rep = ed.health.report()
            assert rep["detections"] == 1, rep
            assert rep["stale_exchanges"] == 1, rep
            assert rep["max_staleness_seen"] == 1, rep
            assert rep["suspect"], rep
            # corruption hit after the last sweeps: m bitwise unaffected
            assert (np.asarray(ed.global_spins(sd)) ==
                    np.asarray(eb.global_spins(sb))).all()
            np.testing.assert_array_equal(Eb, Ed)
            # drop arm == corrupt arm bitwise: NOTHING was ingested
            codes_d = np.zeros(10, np.int32); codes_d[-1] = 1
            e2, s2, _ = run(prec, "stale_hold:4", codes_d)
            np.testing.assert_array_equal(np.asarray(sd.ghosts),
                                          np.asarray(s2.ghosts))
            # quarantine/resync: bitwise the no-fault ghost state
            sr = ed.resync(sd)
            np.testing.assert_array_equal(np.asarray(sr.ghosts),
                                          np.asarray(sb.ghosts))
            assert not ed.health.suspect
            assert ed.health.resyncs == 1
            # fail_fast raises at first detection
            try:
                run(prec, "fail_fast", codes)
                raise SystemExit("fail_fast did not raise")
            except StateCorruption:
                pass
            # freeze_boundary: holds ALL sources after first detection
            codes_f = np.zeros(10, np.int32); codes_f[4] = 2
            ef, sf, _ = run(prec, "freeze_boundary", codes_f)
            repf = ef.health.report()
            assert repf["detections"] == 1, repf
            assert repf["stale_exchanges"] == 6, repf
            print(prec, "dsim_dist acceptance ok")
        """)


def test_lattice_poisoned_exchange_2dev():
    """Same acceptance on the lattice engine's halo fabric: per-face
    integrity headers ride the same ppermute as the payload."""
    run_py("""
        import numpy as np
        import jax
        assert jax.device_count() == 2
        from repro.core.lattice import build_ea3d_lattice
        from repro.core.lattice_dsim import LatticeDSIM
        from repro.core.degrade import StateCorruption
        from repro.core.annealing import ea_schedule
        from repro.compat import make_mesh, auto_axes

        prob = build_ea3d_lattice(4, seed=7)
        mesh = make_mesh((2,), ("data",), axis_types=auto_axes(1))
        sch = ea_schedule(40)

        def run(prec, degrade=None, codes=None):
            e = LatticeDSIM(prob, mesh, dim_axes=("data", None, None),
                            impl="ref", replicas=3, precision=prec,
                            degrade=degrade)
            st = e.init_state(seed=3)
            if codes is not None:
                e.set_exchange_faults(codes)
            st, (_, E) = e.run_recorded(st, sch, [40], sync_every=4)
            return e, st, np.asarray(E)

        def halos_np(st):
            return [np.asarray(h) for h in st.halos]

        for prec in ("int8", "bitplane"):
            eb, sb, Eb = run(prec)
            codes = np.zeros(10, np.int32); codes[-1] = 2
            ed, sd, Ed = run(prec, "stale_hold:4", codes)
            rep = ed.health.report()
            assert rep["detections"] == 1, rep
            assert rep["stale_exchanges"] == 1, rep
            assert rep["suspect"], rep
            assert (np.asarray(ed.global_spins(sd)) ==
                    np.asarray(eb.global_spins(sb))).all()
            np.testing.assert_array_equal(Eb, Ed)
            # drop arm == corrupt arm bitwise (nothing ingested)
            codes_d = np.zeros(10, np.int32); codes_d[-1] = 1
            e2, s2, _ = run(prec, "stale_hold:4", codes_d)
            for a, b in zip(halos_np(sd), halos_np(s2)):
                np.testing.assert_array_equal(a, b)
            # resync -> bitwise the no-fault halos
            sr = ed.resync(sd)
            for a, b in zip(halos_np(sr), halos_np(sb)):
                np.testing.assert_array_equal(a, b)
            assert not ed.health.suspect and ed.health.resyncs == 1
            try:
                run(prec, "fail_fast", codes)
                raise SystemExit("fail_fast did not raise")
            except StateCorruption:
                pass
            codes_f = np.zeros(10, np.int32); codes_f[4] = 2
            ef, sf, _ = run(prec, "freeze_boundary", codes_f)
            repf = ef.health.report()
            assert repf["detections"] == 1, repf
            assert repf["stale_exchanges"] == 6, repf
            print(prec, "lattice acceptance ok")
        """)
