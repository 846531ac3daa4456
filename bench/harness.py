"""One run of one benchmark cell, driven by ``BENCHMARK.json``.

Everything that belongs to a cell is found by name:

* ``BENCHMARK.json`` (checkout root) names the cell's configuration,
  traffic mix and metrics;
* a configuration is the JSON file that its ``configs`` entry names;
* a traffic mix is ``bench/traffic/<traffic>.json``; its ``driver`` key
  names the generator in ``bench/drivers/<driver>.py`` that reads it;
* a per-layer metric is ``bench/metrics/<metric name>.py``, whose
  ``read(ctx)`` returns a number or None (nothing to read there).

A driver module exposes ``Cell(cfg, traffic, seed, log)`` with
``setup()``, ``window(seconds, tick) -> {end-to-end metric: value}``
(``tick()`` lets the profiler close early), ``layer_inputs() -> dict``
and ``check() -> (attempted, failed, {check: (value, limit)})``; the
harness times set-up, wraps the window in the profiler when
``--trace 1``, reads the device memory peak before the reference runs,
and prints the result line.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Optional

T_START = time.perf_counter()

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
TRACE_SECONDS = 2.0


class NoChip(RuntimeError):
    """The machine lacks what the cell asks for; no result is printed."""


def log(msg: str) -> None:
    """A progress line, stamped with the seconds since the process began
    (so that the phases of set-up can be told apart)."""
    print(f"[{time.perf_counter() - T_START:9.3f}] {msg}", flush=True)


def load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{name}: no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, root: str = ROOT):
    """(spec, cell, cfg, traffic) of one workload of ``BENCHMARK.json``."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(root, "bench", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return spec, cell, cfg, traffic


def device_info(chips: int, peaks_path: str = os.path.join(BENCH,
                                                           "peaks.json")):
    """(devices, peaks of their kind); raises NoChip off a TPU, with too
    few chips, or for a device kind the table of peaks lacks."""
    import jax
    devs = jax.devices()
    kind = devs[0].device_kind
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found platform "
                     f"{devs[0].platform!r} ({kind}); no fallback")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devs)}")
    with open(peaks_path) as f:
        peaks = json.load(f)
    if kind not in peaks:
        raise NoChip(f"device kind {kind!r} has no entry in "
                     f"{os.path.basename(peaks_path)}")
    return devs[:chips], peaks[kind]


def enable_cache() -> str:
    """The program's persistent compile cache (``repro.cache``: a fixed
    directory in the checkout unless JAX_COMPILATION_CACHE_DIR is set),
    with every program written to it, so that only a checkout's first run
    compiles.  JAX's own rule writes only compiles of 1 s or more; the
    program compiles ``LatticeDSIM._refresh_halos`` anew in each anneal,
    in about that time, so under that rule whether a window compiles it
    would depend on what earlier runs happened to write."""
    import jax
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.cache import enable_compile_cache
    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class CompileCounter:
    """Counts JAX compilations and persistent-cache loads while armed."""

    def __init__(self):
        import jax
        self.armed = False
        self.compiles = self.loads = self.traces = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, duration: float, **kw) -> None:
        if self.armed and event.endswith("backend_compile_duration"):
            self.compiles += 1
        if self.armed and event.endswith("jaxpr_trace_duration"):
            self.traces += 1

    def _event(self, event: str, **kw) -> None:
        if self.armed and event.endswith("compilation_cache/cache_hits"):
            self.loads += 1


class TraceWindow:
    """Profiles the first ``seconds`` of the measured window, as far as
    the driver calls :meth:`poll` from its loop (a device-bound loop
    launches ~10^5 operations a second, and writing their trace stalls the
    host for several seconds per second traced); :meth:`stop` closes it
    at the latest.  ``window_s`` is the traced span, before the export."""

    def __init__(self, directory: str, seconds: float):
        import jax
        self.seconds, self.window_s = seconds, None
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # keep host overhead low
        jax.profiler.start_trace(directory, profiler_options=opts)
        self.t0 = time.perf_counter()

    def poll(self) -> None:
        if self.window_s is None and \
                time.perf_counter() - self.t0 >= self.seconds:
            self.stop()

    def stop(self) -> None:
        if self.window_s is None:
            import jax
            self.window_s = time.perf_counter() - self.t0
            jax.profiler.stop_trace()


def memory_peak(devs) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else None


def layer_metrics(spec: dict, workload: str) -> list:
    return [m for m in spec["per_layer"]
            if workload in m.get("workloads", [workload])]


def trace_context(trace_dir: str, window_s: float, n_dev: int) -> dict:
    """Device time of a traced window, reduced (see ``xplane``)."""
    import xplane
    devices, host = xplane.load(xplane.find(trace_dir))
    if not any(devices.values()):
        return {}                        # no device operation to read
    all_ops = [e for evs in devices.values() for e in evs]
    busy = {d: xplane.busy_ns(evs) / 1e9 for d, evs in devices.items()}
    kernels = xplane.per_name(all_ops)
    top = sorted(((n, v) for n, v in kernels.items()
                  if n not in xplane.CONTAINERS),
                 key=lambda kv: -kv[1][1])[:10]
    gaps = []
    for evs in devices.values():
        if evs:
            t0 = min(t for _, t, _ in evs)
            t1 = max(t + dd for _, t, dd in evs)
            gaps += xplane.gaps(evs, t0, t1)
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    return {
        "window_s": window_s,
        "busy_s": sum(busy.values()) / max(n_dev, 1),
        "busy_total_s": sum(busy.values()),
        "n_dev": n_dev,
        "kernels": kernels,
        "breakdown": {"device_ops": [[n, s / 1e9] for n, (_, s) in top],
                      "idle_gaps": [[xplane.name_gap(g, host),
                                     (g[1] - g[0]) / 1e9] for g in gaps]},
    }


def run_cell(spec: dict, cell: dict, cfg: dict, traffic: dict, seed: int,
             seconds: float, trace: bool, peaks: dict, devs) -> dict:
    """Set up, measure and check one run; returns the result object."""
    sys.path.insert(0, BENCH)
    driver = load_module(
        os.path.join(BENCH, "drivers", traffic["driver"] + ".py"),
        "driver_" + traffic["driver"])
    counter = CompileCounter()
    run = driver.Cell(cfg, traffic, seed, log)
    run.setup()
    setup_s = time.perf_counter() - T_START
    log(f"setup_s {setup_s:.6f}")

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        counter.armed = True
        tw = TraceWindow(trace_dir, min(TRACE_SECONDS, seconds)) \
            if trace else None
        t0 = time.perf_counter()
        e2e = run.window(seconds, tw.poll if tw else lambda: None)
        window_s = time.perf_counter() - t0
        if tw:
            tw.stop()
        counter.armed = False
        # JAX's compile event also wraps a persistent-cache hit
        log(f"window: {window_s:.6f} s; compiled by XLA "
            f"{counter.compiles - counter.loads}, loaded from the compile "
            f"cache {counter.loads}, traced {counter.traces} inside it")
        mem = memory_peak(devs)
        ctx = dict(run.layer_inputs(), peaks=peaks, cfg=cfg,
                   traffic=traffic)
        if tw:
            t = time.perf_counter()
            ctx.update(trace_context(trace_dir, tw.window_s, len(devs)))
            log(f"trace of {tw.window_s:.3f} s reduced in "
                f"{time.perf_counter() - t:.3f} s")
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    attempted, failed, checks = run.check()
    correct = all(v <= lim for v, lim in checks.values())

    if trace:
        metrics = {}
        for m in layer_metrics(spec, cell["name"]):
            mod = load_module(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"),
                              "metric_" + m["name"].replace(".", "_"))
            val = mod.read(ctx)
            if val is not None:
                metrics[m["name"]] = {"value": val, "unit": m["unit"]}
    else:
        metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
        for m in spec["end_to_end"]:
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}

    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if trace:
        device.update(busy_s=ctx.get("busy_s", 0.0), window_s=tw.window_s)
        out["breakdown"] = ctx.get("breakdown", {})
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec, cell, cfg, traffic = load_cell(args.workload)
        devs, peaks = device_info(int(cell["chips"]))
    except (NoChip, KeyError, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    d = devs[0]
    log(f"device: {d.platform} {d.device_kind} x{len(devs)}")
    log(f"compile cache: {enable_cache()}")
    out = run_cell(spec, cell, cfg, traffic, args.seed, args.seconds,
                   bool(args.trace), peaks, devs)
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0
