"""Observability subsystem: metrics registry under concurrency, span
tracing, the EtaMeter against commcost, the server's metrics surface,
and a 2-device dsim_dist measured-η run (subprocess, forced devices)."""

import json
import os
import re
import subprocess
import sys
import textwrap
import threading

import numpy as np
import pytest

from repro.core import commcost
from repro.core.coloring import lattice3d_coloring
from repro.core.graph import ea3d
from repro.obs import (DEFAULT_TIME_BUCKETS, EtaMeter, MetricsRegistry,
                       Tracer, exchanges_per_sweep, install, span)
from repro.serve import SampleServer

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


# -- metrics registry ---------------------------------------------------------

# Prometheus text exposition: every sample line is name{labels} value
_SAMPLE_RE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z_]\w*="[^"]*"'
    r'(,[a-zA-Z_]\w*="[^"]*")*\})? \S+$')


def _assert_exposition_parses(text: str):
    assert text.endswith("\n")
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        assert _SAMPLE_RE.match(line), f"unparseable sample line: {line!r}"


def test_registry_concurrent_writers_exact_totals():
    """>= 8 writer threads hammer one counter family (labeled + no-label)
    and one histogram while a reader renders snapshots and text; no
    increment is lost and every exposition parses."""
    reg = MetricsRegistry()
    c = reg.counter("hits_total", "hammered counter")
    h = reg.histogram("lat_seconds", "hammered histogram")
    writers, per_writer = 8, 2000
    stop = threading.Event()
    reader_errors = []

    def write(i):
        child = c.labels(worker=str(i % 4))
        for k in range(per_writer):
            c.inc()
            child.inc(2.0)
            h.observe(1e-4 * (k % 50))

    def read():
        while not stop.is_set():
            try:
                snap = reg.snapshot()
                json.dumps(snap)                 # JSON-able mid-write
                _assert_exposition_parses(reg.render_text())
            except Exception as e:              # noqa: BLE001
                reader_errors.append(e)
                return

    rt = threading.Thread(target=read)
    rt.start()
    ts = [threading.Thread(target=write, args=(i,)) for i in range(writers)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    stop.set()
    rt.join()
    assert not reader_errors
    assert c.value == writers * per_writer       # no-label child exact
    total_labeled = sum(child.value for key, child in c.series()
                        if dict(key).get("worker") is not None)
    assert total_labeled == writers * per_writer * 2.0
    assert h.count == writers * per_writer
    # final exposition carries the exact totals
    text = reg.render_text()
    assert f"lat_seconds_count {writers * per_writer}" in text
    _assert_exposition_parses(text)


def test_registry_kinds_and_snapshot_shape():
    reg = MetricsRegistry()
    g = reg.gauge("depth", "queue depth")
    g.set(3)
    g.labels(engine="dsim").set(7)
    reg.counter("depth2")                        # distinct name ok
    with pytest.raises(ValueError):
        reg.counter("depth")                     # kind clash
    with pytest.raises(ValueError):
        reg.counter("c").inc(-1)                 # counters only go up
    h = reg.histogram("h", buckets=(1.0, 2.0))
    h.observe(0.5)
    h.observe(5.0)                               # lands in +Inf bucket
    snap = reg.snapshot()
    assert snap["depth"]["type"] == "gauge"
    assert {"labels": {}, "value": 3.0} in snap["depth"]["series"]
    hs = snap["h"]["series"][0]
    assert hs["count"] == 2 and hs["buckets"][-1] == ["+Inf", 2]
    # +Inf observations clamp percentiles to the last finite bound
    assert h.quantile(0.99) == 2.0
    assert np.isnan(reg.histogram("h2").quantile(0.5))


def test_histogram_percentiles_interpolate():
    reg = MetricsRegistry()
    h = reg.histogram("t", buckets=DEFAULT_TIME_BUCKETS)
    for v in np.linspace(1e-4, 9e-4, 200):
        h.observe(float(v))
    # true p50 = 5e-4; bucket interpolation stays within the owning
    # bucket (2.5e-4, 5e-4] .. (5e-4, 1e-3] span
    assert 2.5e-4 <= h.quantile(0.5) <= 1e-3
    assert h.quantile(0.99) <= 1e-3
    assert h.quantile(0.0) <= h.quantile(0.5) <= h.quantile(1.0)


# -- tracer -------------------------------------------------------------------

def test_tracer_spans_nest_and_export():
    clk = iter(np.arange(0.0, 100.0, 0.5))
    synced = []
    tr = Tracer(clock=lambda: float(next(clk)), capacity=8,
                block=synced.append)
    with tr.span("outer", job="j1") as outer:
        with tr.span("inner") as inner:
            inner.set(chunk=3)
            inner.sync({"state": 1})
    spans = tr.spans()
    assert [s["name"] for s in spans] == ["inner", "outer"]
    by = {s["name"]: s for s in spans}
    assert by["inner"]["parent_id"] == by["outer"]["span_id"]
    assert by["inner"]["attrs"] == {"chunk": 3}
    assert by["outer"]["attrs"] == {"job": "j1"}
    assert by["inner"]["duration_s"] == pytest.approx(0.5)  # one tick
    assert synced == [{"state": 1}]             # block ran before t1
    assert tr.durations("outer") == [pytest.approx(1.5)]
    # bounded ring: old spans evicted
    for i in range(20):
        with tr.span(f"s{i}"):
            pass
    assert len(tr.spans()) == 8


def test_hot_path_span_is_a_bare_annotation_until_installed():
    from jax.profiler import TraceAnnotation
    prev = install(None)
    try:
        assert type(span("cursor.chunk")) is TraceAnnotation
        tr = Tracer()
        install(tr)
        with span("cursor.chunk"):
            with span("cursor.readout"):
                pass
    finally:
        install(prev)
    spans = tr.spans()
    assert [s["name"] for s in spans] == ["cursor.readout", "cursor.chunk"]
    assert spans[0]["parent_id"] == spans[1]["span_id"]
    assert all(s["attrs"] == {} for s in spans)


# -- EtaMeter vs commcost -----------------------------------------------------

def test_exchanges_per_sweep():
    assert exchanges_per_sweep("phase", 3) == 3.0
    assert exchanges_per_sweep(None, 3) == 1.0
    assert exchanges_per_sweep(4, 3) == 0.25
    with pytest.raises(ValueError):
        exchanges_per_sweep(0, 3)


def test_eta_meter_fake_clock_vs_commcost():
    """Hand-computable accounting: t_ex = 0.02 s, chunk of 8 sweeps in
    0.84 s at sync_every=4 -> 2 exchanges -> t_pbit = (0.84 - 0.04)/8 =
    0.1 s, η = 5.0, threshold = 2 * n_color * c_max = 16 (commcost),
    margin = 0.3125."""
    m = EtaMeter(n_color=2, c_max=4, sync_every=4)
    assert np.isnan(m.t_exchange_s) and np.isnan(m.eta)
    m.record_exchange(0.2, count=10)
    m.record_chunk(sweeps=8, seconds=0.84)
    assert m.t_exchange_s == pytest.approx(0.02)
    assert m.t_pbit_sweep_s == pytest.approx(0.1)
    assert m.f_comm_hz == pytest.approx(50.0)
    assert m.f_pbit_hz == pytest.approx(10.0)
    assert m.eta == pytest.approx(5.0)
    assert m.eta_threshold == commcost.eta_threshold(2, 4) == 16.0
    r = m.report()
    assert r["measured_eta"] == pytest.approx(5.0)
    assert r["margin"] == pytest.approx(5.0 / 16.0)
    assert r["behaves_unpartitioned"] is False
    assert r["chunks_recorded"] == 1 and r["sweeps_recorded"] == 8
    assert r["exchanges_attributed"] == pytest.approx(2.0)

    # a fast enough exchange clears the bound: margin >= 1
    fast = EtaMeter(n_color=2, c_max=4, sync_every=4)
    fast.record_exchange(0.2, count=10000)       # t_ex = 2e-5
    fast.record_chunk(sweeps=8, seconds=0.84)
    rf = fast.report()
    assert rf["margin"] >= 1.0 and rf["behaves_unpartitioned"] is True

    # the floor: a mismeasured (too large) t_ex can never produce a
    # negative p-bit time — floored at a tenth of the raw per-sweep time
    bad = EtaMeter(n_color=2, c_max=4, sync_every=1)
    bad.record_exchange(10.0, count=10)
    bad.record_chunk(sweeps=8, seconds=0.8)
    assert bad.t_pbit_sweep_s == pytest.approx(0.1 * 0.8 / 8)


def test_eta_meter_hooks_into_cursor():
    """attach() installs the meter on the recorded cursor's chunk_timer
    (the same hook surface fault injection uses) and accumulates every
    recorded chunk of a real anneal."""
    from repro.core.annealing import constant_schedule
    from repro.engines import make_engine

    h = make_engine("gibbs", ea3d(3, seed=0),
                    coloring=lattice3d_coloring(3), rng="lfsr")
    sch = constant_schedule(2.0, 64)
    cur = h.start_recorded(h.init_state(seed=0), sch, [8, 16], sync_every=1)
    m = EtaMeter(n_color=2, sync_every=1).attach(cur)
    assert cur.chunk_timer == m.on_chunk
    while not cur.done:
        cur.advance(1)
    r = m.report()
    assert r["chunks_recorded"] == 2 and r["sweeps_recorded"] == 16
    assert r["chunk_seconds"] > 0
    assert np.isfinite(r["f_pbit_hz"])           # no exchange side needed


def test_eta_meter_2device_dsim_dist():
    """The acceptance run: a 2-device dsim_dist engine (K=2 slab) reports
    measured η, f_comm, f_pbit, and the margin vs commcost.eta_threshold
    from the EtaMeter, all finite and self-consistent."""
    out = run_py("""
        import json
        import numpy as np
        from repro.compat import auto_axes, make_mesh
        from repro.core.annealing import constant_schedule
        from repro.core.coloring import lattice3d_coloring
        from repro.core.graph import ea3d
        from repro.core.partition import slab_partition
        from repro.engines import make_engine
        from repro.obs import dist_eta_meter

        L = 4
        g = ea3d(L, seed=7)
        h = make_engine("dsim_dist", g, coloring=lattice3d_coloring(L),
                        K=2, labels=slab_partition(L, 2),
                        mesh=make_mesh((2,), ("data",),
                                       axis_types=auto_axes(1)),
                        rng="lfsr", replicas=4)
        meter = dist_eta_meter(h.eng, sync_every=8)
        sch = constant_schedule(3.0, 8 * 64)
        h.run_recorded(h.init_state(seed=0), sch, [32, 64],
                       sync_every=8)                  # compile
        st = h.init_state(seed=0)
        meter.measure_exchange(
            lambda: h.eng.boundary_exchange_fn()(st), reps=16)
        cur = h.start_recorded(st, sch, [32, 64], sync_every=8)
        meter.attach(cur)
        while not cur.done:
            cur.advance(1)
        print(json.dumps(meter.report()))
    """)
    r = json.loads(out.strip().splitlines()[-1])
    for f in ("measured_eta", "eta_threshold", "margin", "f_comm_hz",
              "f_pbit_hz", "t_exchange_s", "t_pbit_sweep_s"):
        assert np.isfinite(r[f]) and r[f] > 0, (f, r)
    # threshold is the commcost bound for the ACTUAL K=2 slab partition
    g = ea3d(4, seed=7)
    from repro.core.partition import slab_partition
    b = commcost.boundary_matrix(np.asarray(g.idx), np.asarray(g.w),
                                 slab_partition(4, 2), 2)
    cc = commcost.comm_cost(b, commcost.RingTopology(k=2, pins_per_link=1))
    assert r["eta_threshold"] == pytest.approx(
        commcost.eta_threshold(r["n_color"], cc.c_max))
    assert r["margin"] == pytest.approx(
        r["measured_eta"] / r["eta_threshold"])
    assert r["measured_eta"] == pytest.approx(
        r["f_comm_hz"] / r["f_pbit_hz"], rel=1e-6)
    assert r["sweeps_recorded"] == 64 and r["chunks_recorded"] == 2
    assert r["behaves_unpartitioned"] == (r["margin"] >= 1.0)


# -- server surface -----------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    """One tiny mixed workload; the metrics surface is inspected by
    several tests."""
    g = ea3d(4, seed=3)
    srv = SampleServer(max_replicas_per_call=8)
    srv.register_problem("p", graph=g, coloring=lattice3d_coloring(4),
                         rng="lfsr")
    ids = [srv.submit("p", engine="gibbs", sweeps=32, replicas=2, seed=s)
           for s in (0, 1)]
    ids.append(srv.submit("p", engine="dsim", sweeps=32, replicas=2,
                          seed=2, sync_every=4))
    srv.drain()
    results = [srv.result(j) for j in ids]
    return srv, results


def test_server_metrics_surface(served):
    """stats() is a registry view; the snapshot and Prometheus text cover
    queue wait, pump latency, goodput, retries/breaker, per-engine
    flips/s."""
    srv, results = served
    assert all(r["status"] == "done" for r in results)
    s = srv.stats()
    snap = srv.metrics_snapshot()
    # counters migrated onto the registry: stats() mirrors family values
    assert s["completed"] == 3
    assert snap["serve_jobs_completed_total"]["series"][0]["value"] == 3
    assert s["submitted"] == sum(
        e["value"] for e in snap["serve_jobs_submitted_total"]["series"])
    # latency/goodput histograms observed per engine
    for fam in ("serve_queue_wait_seconds", "serve_pump_chunk_seconds",
                "serve_job_total_seconds", "serve_job_flips_per_s"):
        engines = {e["labels"].get("engine") for e in snap[fam]["series"]}
        assert {"gibbs", "dsim"} <= engines, fam
        assert sum(e["count"] for e in snap[fam]["series"]) >= 2, fam
        assert all("p50" in e and "p99" in e for e in snap[fam]["series"])
    # per-engine flips/s gauge
    rates = {(e["labels"]["engine"], e["labels"]["precision"]): e["value"]
             for e in snap["engine_flips_per_s"]["series"]}
    assert all(v > 0 for v in rates.values()) and len(rates) >= 2
    # pool + scheduler instrumentation share the registry
    assert sum(e["value"] for e in snap["pool_misses_total"]["series"]) \
        == s["pool"]["misses"]
    assert sum(e["count"] for e in snap["pool_build_seconds"]["series"]) \
        == s["pool"]["misses"]
    assert sum(e["count"]
               for e in snap["sched_pack_width_replicas"]["series"]) \
        == s["scheduler"]["batches_formed"]
    assert s["scheduler"]["padding_replicas"] >= 0
    # Prometheus text: parseable, and the catalogue is present
    text = srv.render_metrics()
    _assert_exposition_parses(text)
    for name in ("serve_jobs_completed_total", "serve_queue_wait_seconds_bucket",
                 "serve_pump_chunk_seconds_count", "serve_job_flips_per_s_sum",
                 "engine_flips_per_s", "pool_hits_total",
                 "sched_pack_width_replicas_bucket", "serve_queue_depth",
                 "serve_retries_total", "pool_open_circuits"):
        assert name in text, name
    # pump.chunk spans recorded with engine attribution
    chunk_spans = srv.tracer.spans("pump.chunk")
    assert len(chunk_spans) >= 2
    assert all(sp["duration_s"] > 0 and "engine" in sp["attrs"]
               for sp in chunk_spans)


def test_server_stats_snapshot_is_isolated(served):
    """Satellite regression: mutating the returned stats() dict (top
    level and nested pool/scheduler/spool views) cannot corrupt server
    state."""
    srv, _ = served
    before = srv.stats()
    victim = srv.stats()
    victim["completed"] = 10 ** 9
    victim["pool"].clear()
    victim["scheduler"]["batches_formed"] = -1
    if isinstance(victim["spool"], dict):
        victim["spool"].clear()
    victim.clear()
    after = srv.stats()
    assert after == before
    assert after["pool"]["misses"] == before["pool"]["misses"]
    # the counters really live on the registry, not the mutated dict
    assert srv.completed == before["completed"]


def test_legacy_counter_attributes_still_read(served):
    srv, _ = served
    assert srv.completed == 3 and srv.failed == 0 and srv.retries == 0
    with pytest.raises(AttributeError):
        srv.not_a_counter
