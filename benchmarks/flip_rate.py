"""Methods — flip-rate measurement.

The paper's flip rate = N p-bits updated per local clock (all N flip
attempts per sweep), measured with on-chip counters.  Here: measured
sweeps/s x N x R for every registry engine at equal problem size, with the
lattice path measured through the fused multi-phase kernel (f32 and the
fixed-point int8 pipeline) and through the seed's per-phase reference
dispatch (one launch per color phase).

Every timing is reported as best-of-N *plus* the per-run spread
(min/median/max AND the trimmed median over the reps) — this container's
scheduler swings ~2x run to run, so a bare best-of number is unreadable
without the spread — and the JSON carries a host fingerprint for cross-run
comparability.  Engine-level reps are INTERLEAVED across paths (rep i of
every path runs before rep i+1 of any), so host drift hits all paths
equally and path-vs-path ratios are apples to apples; the rep count is
recorded per path.

Writes the usual reports/bench/flip_rate.json detail plus BENCH_flip_rate.json
at the repo root recording the fused-vs-per-phase and int8-vs-f32 speedups
against the seed lattice path (schema checked in CI by
tools/check_bench_schema.py).
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

from repro.engines import make_engine
from repro.core.graph import ea3d
from repro.core.coloring import lattice3d_coloring
from repro.core.partition import slab_partition
from repro.core.annealing import constant_schedule

from .common import eta_probe, host_fingerprint, row, save_detail

ROOT_BENCH = os.path.join(os.path.dirname(__file__), "..",
                          "BENCH_flip_rate.json")
SYNC = 8          # the seed benchmark's boundary-exchange period




def _rates_interleaved(handles: dict, sweeps: int, sync_of: dict,
                       reps: int = 9) -> dict:
    """Throughput of every path with spread, reps interleaved across paths.

    On a contended host every disturbance only slows a rep down, so the max
    over reps ("best") is the least-biased throughput estimate — but the
    min/median/max spread is what says whether a comparison is signal or
    scheduler noise, and interleaving (rep i of every path before rep i+1
    of any) is what makes the path-vs-path ratios robust to drift: a
    CPU-frequency or cgroup swing lands on all paths, not one.
    """
    sch = constant_schedule(3.0, 8 * sweeps)
    for name, h in handles.items():               # compile outside the reps
        st = h.init_state(seed=0)
        h.run_recorded(st, sch, [sweeps], sync_every=sync_of[name])
    vals = {name: [] for name in handles}
    for _ in range(reps):
        for name, h in handles.items():
            st = h.init_state(seed=0)
            t0 = time.perf_counter()
            h.run_recorded(st, sch, [sweeps], sync_every=sync_of[name])
            vals[name].append(sweeps / (time.perf_counter() - t0))
    return {name: _stats(v) for name, v in vals.items()}


def _stats(vals) -> dict:
    """best-of-N plus spread; ``trimmed_median`` drops the one fastest and
    one slowest rep before the median — a robust center the best-of number
    is read against (reps also recorded, per the schema)."""
    vals = sorted(float(v) for v in vals)
    trimmed = vals[1:-1] if len(vals) >= 3 else vals
    return {"best": float(np.max(vals)), "min": float(np.min(vals)),
            "median": float(np.median(vals)),
            "trimmed_median": float(np.median(trimmed)),
            "max": float(np.max(vals)), "reps": int(len(vals))}


def _kernel_head_to_head(L: int, reps: int = 15) -> dict:
    """Kernel-layer flips/s of the fused sweep op, f32 vs int8, at equal
    (L, R=1, sync_every=S halos held fixed).

    Reps interleave the two precisions so host drift hits both equally —
    the end-to-end engine numbers fold both pipelines into one fused
    XLA program whose shared traffic (neighbor concats, xorshift, masked
    writes) hides the update-rule cost; this is the measurement of the
    update rule itself.
    """
    import jax
    import jax.numpy as jnp
    from repro.core.lattice import build_ea3d_lattice
    from repro.core.pbit import quantize_couplings, field_bound, threshold_lut
    from repro.kernels.ref import pbit_brick_sweep_ref, pbit_brick_sweep_int_ref

    p = build_ea3d_lattice(L)
    rng = np.random.default_rng(0)
    m = jnp.asarray(rng.choice([-1, 1], size=p.dims).astype(np.int8))
    s = jnp.asarray(rng.integers(1, 2 ** 32, size=p.dims, dtype=np.uint32))
    halos = tuple(jnp.zeros((L, L), jnp.int8) for _ in range(6))
    betas = jnp.full((SYNC,), 3.0, jnp.float32)
    h_q, w6_q, scale = quantize_couplings(p.h, p.w6)
    lut = jnp.asarray(threshold_lut([3.0], scale, field_bound(h_q, w6_q)))
    rows = jnp.zeros((SYNC,), jnp.int32)
    fns = {
        "f32": jax.jit(lambda m, s: pbit_brick_sweep_ref(
            m, s, betas, p.masks, p.h, p.w6, halos, None)),
        "int8": jax.jit(lambda m, s: pbit_brick_sweep_int_ref(
            m, s, rows, p.masks, h_q, w6_q, halos, lut)),
    }
    calls = max(1, (1 << 21) // (L ** 3 * SYNC))   # ~2M flips per rep
    for fn in fns.values():
        jax.block_until_ready(fn(m, s))
    times = {k: [] for k in fns}
    for _ in range(reps):
        for k, fn in fns.items():                  # interleaved
            t0 = time.perf_counter()
            for _ in range(calls):
                o = fn(m, s)
            jax.block_until_ready(o[0])
            times[k].append(L ** 3 * SYNC * calls
                            / (time.perf_counter() - t0))
    out = {"L": L, "sweeps_per_call": SYNC, "calls_per_rep": calls,
           "f32_flips_per_s": _stats(times["f32"]),
           "int8_flips_per_s": _stats(times["int8"])}
    out["speedup_int8_vs_f32"] = (out["int8_flips_per_s"]["best"]
                                  / out["f32_flips_per_s"]["best"])
    return out


def _bitplane_word_scaling_bench(L: int, reps: int = 9) -> dict:
    """Per-lane cost of the word sweep across stacked word planes, at the
    kernel layer (W in {1, 2, 4} interleaved, halos fixed) — the gate that
    stacking planes does not tax the lanes.

    Kernel-layer like ``_kernel_head_to_head`` and for the same reason:
    the end-to-end engine numbers fold per-chunk dispatch and this host's
    scheduler swings into every path, drowning the W-scaling signal; the
    word loop itself is what the multi-word fabric adds, so it is what
    gets measured.  (The engine-level aggregates at R=32/64/128 ride the
    interleaved rep loop and land in ``all_paths_flips_per_s``.)
    """
    import jax
    import jax.numpy as jnp
    from repro.core.lattice import build_ea3d_lattice
    from repro.core.packing import pack_lanes
    from repro.core.pbit import (bitplane_planes, field_bound,
                                 quantize_couplings, threshold_lut)
    from repro.kernels.ref import pbit_bitplane_sweep_ref

    p = build_ea3d_lattice(L)
    rng = np.random.default_rng(0)
    h_q, w6_q, scale = quantize_couplings(p.h, p.w6)
    signs6, nz6, base, _ = bitplane_planes(h_q, w6_q)
    lut = jnp.asarray(threshold_lut([3.0], scale, field_bound(h_q, w6_q)))
    rows = jnp.zeros((SYNC,), jnp.int32)
    masks = np.asarray(p.masks)
    widths, fns, inputs = (1, 2, 4), {}, {}
    for W in widths:
        R = 32 * W
        mw = pack_lanes(jnp.asarray(
            rng.choice([-1, 1], size=(R,) + p.dims).astype(np.int8)))
        s = jnp.asarray(rng.integers(1, 2 ** 32, size=(R,) + p.dims,
                                     dtype=np.uint32))
        # every lane live (R is a word multiple): full masks on all planes
        masks_w = jnp.asarray(
            np.where(masks[:, None] != 0, np.uint32(0xFFFFFFFF),
                     np.uint32(0))[:, [0] * W])
        halos_w = tuple(jnp.zeros((W, L, L), jnp.uint32) for _ in range(6))
        fns[W] = jax.jit(lambda mw, s, mk=masks_w, hl=halos_w:
                         pbit_bitplane_sweep_ref(mw, s, rows, mk, signs6,
                                                 nz6, base, hl, lut))
        inputs[W] = (mw, s)
        jax.block_until_ready(fns[W](mw, s)[0])   # compile outside reps
    calls = max(1, (1 << 19) // (L ** 3 * SYNC))
    rates = {W: [] for W in widths}                # AGGREGATE lane-flips/s
    for _ in range(reps):
        for W in widths:                           # interleaved
            mw, s = inputs[W]
            t0 = time.perf_counter()
            for _ in range(calls):
                o = fns[W](mw, s)
            jax.block_until_ready(o[0])
            rates[W].append(L ** 3 * SYNC * 32 * W * calls
                            / (time.perf_counter() - t0))
    spread = {W: _stats(v) for W, v in rates.items()}
    agg = {f"W{W}_R{32 * W}": spread[W]["best"] for W in widths}
    return {
        "L": L, "sweeps_per_call": SYNC, "calls_per_rep": calls,
        "layer": "kernel (jitted word sweep, halos fixed, interleaved)",
        "note": ("lane_efficiency is PER-LANE COST: aggregate lane-flips/s "
                 "at W planes over aggregate at one plane (on this serial "
                 "host total throughput is the conserved quantity, so the "
                 "wall-clock rate of any single lane divides by W by "
                 "construction; ~1.0 means stacking planes taxes no lane)"),
        "aggregate_lane_flips_per_s": agg,
        "aggregate_lane_flips_per_s_spread":
            {f"W{W}_R{32 * W}": spread[W] for W in widths},
        "per_lane_flips_per_s":
            {f"W{W}_R{32 * W}": agg[f"W{W}_R{32 * W}"] / (32 * W)
             for W in widths},
        "lane_efficiency_vs_one_word": {
            f"W{W}_R{32 * W}": agg[f"W{W}_R{32 * W}"] / agg["W1_R32"]
            for W in widths if W > 1},
    }


def _dist_word_boundary_bench(L: int, sweeps: int, reps: int = 5) -> dict:
    """Mesh-engine word path: dsim_dist bitplane vs *unpacked* int8 at the
    same R=32 width on a one-device mesh (measures the engine path without
    a forced device count; the boundary payload accounting is exact and
    host-independent).  The bitplane all-gather ships native uint32 words —
    4 B per boundary site for all 32 chains, zero pack/unpack on the
    collective path — vs 32 B/site for unpacked int8 planes."""
    from repro.compat import make_mesh, auto_axes
    g = ea3d(L, seed=0)
    col = lattice3d_coloring(L)
    labels = np.zeros(g.n, np.int32)
    mesh = make_mesh((1,), ("data",), axis_types=auto_axes(1))
    mk = lambda prec, **kw: make_engine(
        "dsim_dist", g, coloring=col, K=1, labels=labels, mesh=mesh,
        rng="lfsr", precision=prec, replicas=32, **kw)
    handles = {"dsim_dist_int8_R32": mk("int8", bitpack=False),
               "dsim_dist_bitplane_R32": mk("bitplane")}
    sync_of = {k: SYNC for k in handles}
    spread = _rates_interleaved(handles, sweeps, sync_of, reps=reps)
    flips = {k: v["best"] * g.n * 32 for k, v in spread.items()}
    payloads = {k: h.eng.boundary_payload() for k, h in handles.items()}
    return {
        "L": L, "N": g.n, "replicas": 32, "sync_every": SYNC,
        "sweeps_per_s_spread": spread,
        "lane_flips_per_s": flips,
        "speedup_bitplane_vs_int8_unpacked":
            flips["dsim_dist_bitplane_R32"] / flips["dsim_dist_int8_R32"],
        # the wire format the tentpole gates: bytes one device publishes
        # per boundary site covering ALL 32 chains
        "boundary_bytes_per_site_bitplane_R32":
            payloads["dsim_dist_bitplane_R32"]["bytes_per_site_all_chains"],
        "boundary_bytes_per_site_int8_unpacked_R32":
            payloads["dsim_dist_int8_R32"]["bytes_per_site_all_chains"],
        "boundary_shrink":
            payloads["dsim_dist_int8_R32"]["bytes_per_site_all_chains"]
            / payloads["dsim_dist_bitplane_R32"]["bytes_per_site_all_chains"],
        "payload_dtype": payloads["dsim_dist_bitplane_R32"]["dtype"],
        "pack_compute_bitplane":
            payloads["dsim_dist_bitplane_R32"]["pack_compute"],
    }


def _apt_packed_bench(reps: int = 5, sweeps: int = 24) -> dict:
    """Lane-packed APT+ICM vs the unpacked fixed-point ladder it is
    bit-identical to: a (chains=4) x (temperatures=8) grid = all 32 word
    lanes.  Also times the replica-exchange swap move in isolation — the
    packed move is one lane permutation (bit gather/scatter) per offset
    pass applied to every word, vs the unpacked (P, T, N) where-chain."""
    import jax
    from repro.core.apt_icm import APTICM

    g = ea3d(4, seed=0)
    col = lattice3d_coloring(4)
    betas = np.linspace(0.5, 3.0, 8)
    un = APTICM(g, col, betas, chains=4, rng="lfsr")
    pk = APTICM(g, col, betas, chains=4, rng="lfsr", packed=True)
    engines = {"apt_icm_unpacked": un, "apt_icm_packed": pk}
    for eng in engines.values():                  # compile outside the reps
        eng.run(eng.init_state(seed=0), 2, icm_every=2, record_every=2)
    vals = {k: [] for k in engines}
    for _ in range(reps):
        for k, eng in engines.items():
            st = eng.init_state(seed=0)
            t0 = time.perf_counter()
            eng.run(st, sweeps, icm_every=8, record_every=sweeps)
            vals[k].append(sweeps / (time.perf_counter() - t0))
    # the swap move alone, jitted, per call (best over reps)
    su, sp = un.init_state(seed=0), pk.init_state(seed=0)
    f_un = jax.jit(lambda m, E, k, s: un._exchange(m, E, k, s))
    f_pk = jax.jit(lambda w, E, k, s: pk._exchange_packed(w, E, k, s))
    jax.block_until_ready(f_un(su.m, su.E, su.key, su.swaps))
    jax.block_until_ready(f_pk(sp.m, sp.E, sp.key, sp.swaps))
    swap = {}
    for name, fn, st in (("unpacked_s", f_un, su), ("packed_s", f_pk, sp)):
        ts = []
        for _ in range(max(reps, 3)):
            t0 = time.perf_counter()
            for _ in range(16):
                o = fn(st.m, st.E, st.key, st.swaps)
            jax.block_until_ready(o[0])
            ts.append((time.perf_counter() - t0) / 16)
        swap[name] = float(np.min(ts))
    return {
        "N": g.n, "chains": 4, "temperatures": 8, "lanes": 32,
        "sweeps": sweeps,
        "packed_sweeps_per_s": _stats(vals["apt_icm_packed"]),
        "unpacked_sweeps_per_s": _stats(vals["apt_icm_unpacked"]),
        "speedup_packed_vs_unpacked":
            max(vals["apt_icm_packed"]) / max(vals["apt_icm_unpacked"]),
        "swap_move_cost": swap,
    }


_DEGRADED_SCRIPT = r"""
import json, sys
import numpy as np
from repro.compat import make_mesh, auto_axes
from repro.core import commcost
from repro.core.annealing import ea_schedule
from repro.core.coloring import lattice3d_coloring
from repro.core.graph import ea3d
from repro.core.partition import slab_partition
from repro.engines import make_engine
from repro.obs import EtaMeter
from repro.serve.faults import FaultPlan, FaultRule

L, SYNC, SWEEPS = %(L)d, %(SYNC)d, %(SWEEPS)d
g = ea3d(L, seed=0)
col = lattice3d_coloring(L)
labels = slab_partition(L, 2)
mesh = make_mesh((2,), ("data",), axis_types=auto_axes(1))
h = make_engine("dsim_dist", g, coloring=col, K=2, labels=labels,
                mesh=mesh, rng="lfsr", precision="int8", replicas=1,
                degrade="stale_hold:%(SWEEPS)d")
sch = ea_schedule(SWEEPS)
total = int(sch.total_sweeps)
n_ex = max(total // SYNC, 1)
pts = sorted(set(range(SYNC, total + 1, SYNC)))
b = commcost.boundary_matrix(np.asarray(g.idx), np.asarray(g.w), labels, 2)
cc = commcost.comm_cost(b, commcost.RingTopology(k=2, pins_per_link=1))
meter = EtaMeter(n_color=len(h.eng.p.color_slots), c_max=cc.c_max,
                 sync_every=SYNC)
h.eng.set_exchange_faults(np.zeros(n_ex, np.int32))
h.run_recorded(h.init_state(seed=0), sch, pts,
               sync_every=SYNC)                 # compile outside timing
meter.measure_exchange(
    lambda st=h.init_state(seed=0): h.eng.boundary_exchange_fn()(st))
arms = {}
eta_clean = None
for frac in (0.0, 0.1, 0.3):
    if frac > 0:
        plan = FaultPlan([FaultRule(site="exchange_drop", rate=frac)],
                         seed=12345)
        codes = plan.exchange_codes(n_ex)
    else:
        codes = np.zeros(n_ex, np.int32)   # same traced shape: one trace
    h.eng.set_exchange_faults(codes)
    cur = h.start_recorded(h.init_state(seed=0), sch, pts, sync_every=SYNC)
    if frac == 0.0:
        meter.attach(cur)
    while not cur.done:
        cur.advance(1)
    rec = cur.record()
    rep = h.eng.health.report()
    if frac == 0.0:
        eta_clean = float(meter.eta)
    E = np.asarray(rec.energies)[:, 0]
    arms["%%.1f" %% frac] = {
        "drop_fraction": frac,
        "completed": bool(cur.done),
        "detections": int(rep["detections"]),
        "stale_exchanges": int(rep["stale_exchanges"]),
        "exchanges_total": int(rep["exchanges_total"]),
        "max_staleness_seen": int(rep["max_staleness_seen"]),
        "delivered_fraction": float(rep["delivered_fraction"]),
        # effective eta uses the ONE clean measured eta so the arm-vs-arm
        # comparison isolates the fault process from host timing noise
        "effective_eta": eta_clean * float(rep["delivered_fraction"]),
        "energy_first": float(E[0]),
        "energy_final": float(E[-1]),
        "residual_energy_drop": float(E[0] - E[-1]),
    }
out = {
    "engine": "dsim_dist", "K": 2, "L": L, "N": int(g.n),
    "precision": "int8", "policy": "stale_hold:%(SWEEPS)d",
    "sync_every": SYNC, "exchanges_per_run": n_ex,
    "measured_eta_clean": eta_clean,
    "eta_threshold": float(meter.eta_threshold),
    "arms": arms,
}
print("DEGJSON" + json.dumps(out, default=float))
"""


def _degraded_mesh_bench(sweeps: int) -> dict:
    """Degraded arm of the flip-rate record: a REAL 2-device dsim_dist
    mesh (forced host platform device count, hence the subprocess, which
    is pinned to the CPU: the parent already holds any chip) under
    ``stale_hold`` with 0/10/30% of boundary exchanges dropped at the
    engine fault site — residual-energy decay per arm plus the
    staleness-vs-eta accounting (effective_eta = clean measured eta x
    delivered fraction).  Gated by tools/check_bench_schema.py: all arms
    complete, effective_eta finite and monotone non-increasing in the
    drop fraction, detections > 0 whenever exchanges were dropped."""
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=2").strip()
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    script = _DEGRADED_SCRIPT % {
        "L": 6, "SYNC": SYNC, "SWEEPS": max(min(sweeps // 4, 256), 64)}
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=900)
    if proc.returncode != 0:
        raise RuntimeError(
            f"degraded-mesh bench subprocess failed:\n{proc.stderr[-4000:]}")
    for line in proc.stdout.splitlines():
        if line.startswith("DEGJSON"):
            return json.loads(line[len("DEGJSON"):])
    raise RuntimeError("degraded-mesh bench subprocess printed no record")


def _telemetry_bench(L: int, sweeps: int, flips: dict,
                     reps: int = 9) -> dict:
    """The benchmark's own observability record: the measured-η probe, a
    per-chunk latency histogram from the cursor ``chunk_timer`` hook, and
    the cost of that hook itself — the SAME fused lattice path annealed
    with the timer attached vs detached, reps interleaved so host drift
    hits both arms equally.  The timer brackets every chunk with a
    ``block_until_ready`` pair, so this is the full price of enabling
    chunk telemetry; the gate is < 2% on the trimmed medians."""
    from repro.obs import MetricsRegistry

    eta = eta_probe(L=min(L, 5), sweeps=max(sweeps // 8, 64),
                    sync_every=SYNC)

    reg = MetricsRegistry()
    hist = reg.histogram("bench_chunk_seconds",
                         "recorded-chunk wall time, fused lattice path")
    g_rate = reg.gauge("bench_flips_per_s",
                       "best-of-reps flips/s per engine path")
    for path, v in flips.items():
        g_rate.labels(path=path).set(v)

    h = make_engine("lattice", L=L, seed=0, impl="ref", fused=True,
                    replicas=1)
    # sparse record points (2 per run) over a long anneal: the timer
    # serializes host dispatch against device work at every chunk
    # boundary, so its cost is a fixed ~0.1 ms per chunk — measured at
    # recorded-run granularity (tens of ms per chunk) it amortizes below
    # the 2% gate, while dense record points would charge the pipeline
    # stall to the hook; the long runs also lift each rep well above the
    # host's per-call timing jitter
    total = 8 * sweeps
    sch = constant_schedule(3.0, total)
    step = max(total // 2, 1)
    pts = list(range(step, total + 1, step))

    def _run(timed: bool) -> float:
        cur = h.start_recorded(h.init_state(seed=0), sch, pts,
                               sync_every=SYNC)
        if timed:
            cur.chunk_timer = lambda sw, s: hist.observe(s)
        t0 = time.perf_counter()
        while not cur.done:
            cur.advance(1)
        cur.record()                      # settle device work
        return time.perf_counter() - t0

    _run(True), _run(False)               # compile/warm both arms
    on, off = [], []
    for _ in range(reps):                 # interleaved
        on.append(total / _run(True))
        off.append(total / _run(False))
    s_on, s_off = _stats(on), _stats(off)
    overhead = s_off["trimmed_median"] / s_on["trimmed_median"] - 1.0
    return {
        "eta": eta,
        "overhead": {
            "path": "lattice_kernel (fused, R=1, chunked cursor)",
            "chunks_per_run": len(pts), "sweeps_per_run": total,
            "sweeps_per_s_timer_on": s_on,
            "sweeps_per_s_timer_off": s_off,
            "overhead_fraction": overhead,
            "note": ("trimmed-median slowdown of the chunk_timer hook "
                     "(block_until_ready pair + histogram observe per "
                     "chunk) over the untimed cursor; interleaved reps. "
                     "The bracket serializes host dispatch against "
                     "device work once per chunk (~0.1 ms), amortized "
                     "over recorded-run-sized chunks; values within "
                     "this host's noise band (|x| of a few %) mean "
                     "'below measurement noise', and a negative sign "
                     "is scheduler drift, not a speedup"),
        },
        "metrics": reg.snapshot(),
    }


def run(quick: bool = True, engine: str = None, replicas: int = 1):
    L = 8 if quick else 16
    sweeps = 1024 if quick else 8192
    R = max(int(replicas), 1)
    g = ea3d(L, seed=0)
    col = lattice3d_coloring(L)

    # lazy handle thunks: only the paths that survive the --engine filter
    # are ever constructed (lattice builds are seconds at --full size)
    thunks = {
        "monolithic": lambda: make_engine("gibbs", g, coloring=col,
                                          rng="lfsr", replicas=R),
        "dsim_stacked": lambda: make_engine("dsim", g, coloring=col,
                                            rng="lfsr", K=4,
                                            labels=slab_partition(L, 4),
                                            replicas=R),
        "lattice_kernel": lambda: make_engine("lattice", L=L, seed=0,
                                              impl="ref", fused=True,
                                              replicas=R),
        "lattice_per_phase": lambda: make_engine("lattice", L=L, seed=0,
                                                 impl="ref", fused=False,
                                                 replicas=R),
        # the tentpole path: fixed-point pipeline through the fused kernel
        "lattice_fused_int8": lambda: make_engine("lattice", L=L, seed=0,
                                                  impl="ref", fused=True,
                                                  precision="int8",
                                                  replicas=R),
    }
    if engine == "dsim_dist":
        # single-device shard_map path (K=1): measures the distributed
        # backend's per-chunk overhead without needing a forced device count
        thunks = {"dsim_dist_k1": lambda: make_engine(
            "dsim_dist", g, coloring=col, K=1,
            labels=np.zeros(g.n, np.int32), rng="lfsr", replicas=R)}
    elif engine is not None:
        keep = {"gibbs": ["monolithic"], "dsim": ["dsim_stacked"],
                "lattice": ["lattice_kernel", "lattice_per_phase",
                            "lattice_fused_int8"]}
        names = keep.get(engine, [engine])
        thunks = {k: v for k, v in thunks.items() if k in names}
        if not thunks:
            raise ValueError(f"no flip-rate path for engine {engine!r}")
    handles = {k: mk() for k, mk in thunks.items()}

    n = g.n
    sync_used, rep_of = {}, {}
    for name in handles:
        sync_used[name] = SYNC if "lattice" in name or "dsim" in name else 1
        rep_of[name] = R

    # the replica-parallel production paths: one fused call drives R_BATCH
    # independent chains of the SAME instance (the paper's many-anneals-
    # per-machine operating point; the seed had neither fusion nor
    # replicas), and the bit-plane paths pack 32 lanes into every uint32
    # word — one, two, and four stacked word planes (the multi-word fabric
    # this benchmark gates: per-lane rate must hold as W grows)
    R_BATCH = max(R, 8)
    R_LANES = 32
    if engine in (None, "lattice"):
        for name, prec, rr in [
                (f"lattice_fused_R{R_BATCH}", "f32", R_BATCH),
                (f"lattice_fused_int8_R{R_BATCH}", "int8", R_BATCH),
                (f"lattice_fused_int8_R{R_LANES}", "int8", R_LANES),
                (f"lattice_bitplane_R{R_LANES}", "bitplane", R_LANES),
                (f"lattice_bitplane_R{2 * R_LANES}", "bitplane",
                 2 * R_LANES),
                (f"lattice_bitplane_R{4 * R_LANES}", "bitplane",
                 4 * R_LANES)]:
            handles[name] = make_engine("lattice", L=L, seed=0, impl="ref",
                                        precision=prec, replicas=rr)
            sync_used[name] = SYNC
            rep_of[name] = rr

    # ALL engine-level paths timed in one interleaved rep loop
    spread = _rates_interleaved(handles, sweeps, sync_used)
    out = {k: v["best"] for k, v in spread.items()}

    # kernel-layer head-to-head of the update rule (interleaved reps)
    k2k = None
    if engine in (None, "lattice"):
        k2k = _kernel_head_to_head(16 if quick else 32)

    # the word-lane mesh-engine path and the lane-packed tempering ladder
    # (cheap at quick size; part of the gated record, so they run whenever
    # the record below will be written)
    dist_word = apt_packed = word_scaling = degraded = None
    if R == 1 and engine in (None, "lattice"):
        dist_word = _dist_word_boundary_bench(L, max(sweeps // 4, 256))
        apt_packed = _apt_packed_bench()
        word_scaling = _bitplane_word_scaling_bench(L)
        degraded = _degraded_mesh_bench(sweeps)

    flips = {k: v * n * rep_of[k] for k, v in out.items()}

    # the telemetry record (measured η + chunk-latency histogram + the
    # <2% chunk-timer overhead gate) rides with the gated BENCH record
    telemetry = None
    if R == 1 and engine in (None, "lattice"):
        telemetry = _telemetry_bench(L, sweeps, flips)

    detail = {"L": L, "N": n, "replicas": rep_of, "sync_every": sync_used,
              "host": host_fingerprint(),
              "sweeps_per_s": out, "sweeps_per_s_spread": spread,
              "flips_per_s": flips}
    if "lattice_kernel" in flips and "lattice_per_phase" in flips:
        detail["fused_speedup_vs_per_phase"] = (
            flips["lattice_kernel"] / flips["lattice_per_phase"])
    if k2k is not None:
        detail["kernel_int8_vs_f32"] = k2k
    if dist_word is not None:
        detail["dsim_dist_bitplane"] = dist_word
    if apt_packed is not None:
        detail["apt_icm_packed"] = apt_packed
    if word_scaling is not None:
        detail["bitplane_word_scaling"] = word_scaling
    if degraded is not None:
        detail["degraded_mesh"] = degraded
    if telemetry is not None:
        detail["telemetry"] = telemetry
    save_detail("flip_rate", detail)

    # the seed-comparison record is only meaningful for the canonical R=1
    # run (its baseline key is the seed's single-chain dispatch)
    if R == 1 and "lattice_kernel" in flips and "lattice_per_phase" in flips:
        batch_keys = [k for k in flips if k.startswith("lattice_fused_R")]
        best_batch = max((flips[k] for k in batch_keys),
                         default=flips["lattice_kernel"])
        bp_key = f"lattice_bitplane_R{R_LANES}"
        bp64_key = f"lattice_bitplane_R{2 * R_LANES}"
        bp128_key = f"lattice_bitplane_R{4 * R_LANES}"
        i8_key = f"lattice_fused_int8_R{R_BATCH}"
        bench = {
            "mode": "quick" if quick else "full",
            "problem": {"L": L, "N": n, "sync_every": SYNC},
            "host": host_fingerprint(),
            "seed_lattice_flips_per_s": None,
            "seed_note": ("the seed's lattice flip-rate path cannot run on "
                          "this jax install (jax.shard_map / "
                          "jax.make_mesh(axis_types=...) unsupported — the "
                          "benchmark and engine both crash); "
                          "'lattice_per_phase_R1' below runs the seed's "
                          "exact per-phase single-chain dispatch through "
                          "the restored engine and stands in as the seed "
                          "baseline at equal problem size"),
            "lattice_per_phase_R1_flips_per_s": flips["lattice_per_phase"],
            "lattice_fused_R1_flips_per_s": flips["lattice_kernel"],
            "lattice_fused_int8_R1_flips_per_s": flips["lattice_fused_int8"],
            "lattice_path_flips_per_s": {k: flips[k] for k in flips
                                         if k.startswith("lattice")},
            # separately-labeled speedups: kernel fusion alone at equal
            # R=1, the fixed-point update rule over the f32 rule inside the
            # fused kernel at equal (L, R, sync_every) — measured at the
            # kernel layer with interleaved reps, because end-to-end both
            # pipelines compile into one fused XLA program whose shared
            # traffic masks the update rule on this host (the engine-level
            # ratio is recorded alongside) — and the full new operating
            # point (fusion + replica batch — aggregate chain-flips, not a
            # per-chain kernel speedup)
            "speedup_fused_R1_vs_seed_dispatch":
                flips["lattice_kernel"] / flips["lattice_per_phase"],
            "speedup_int8_vs_f32_fused_R1": k2k["speedup_int8_vs_f32"],
            "speedup_int8_vs_f32_fused_R1_note": (
                "kernel-layer measurement (fused sweep op, halos fixed, "
                "interleaved reps; see kernel_int8_vs_f32); "
                "engine_speedup_int8_vs_f32_R1 is the end-to-end ratio, "
                "fusion- and noise-dominated on this host"),
            "engine_speedup_int8_vs_f32_R1":
                flips["lattice_fused_int8"] / flips["lattice_kernel"],
            "kernel_int8_vs_f32": k2k,
            "speedup_fused_replica_batch_vs_seed_dispatch":
                best_batch / flips["lattice_per_phase"],
            # the multi-spin-coded operating point: 32 replica lanes per
            # uint32 word, one word sweep per call.  Aggregate lane-flips
            # vs the int8 R=8 replica batch (both interleaved in the same
            # rep loop on this host), plus the per-lane rates — a packed
            # lane must cost no more than an unpacked int8 replica at the
            # SAME batch width (R=32), which is the apples-to-apples lane
            # comparison; the R=8 batch is int8's small-batch sweet spot
            # on this 2-core container (per-replica rate FALLS with R for
            # the unpacked paths, while the word path holds at 32)
            f"{bp_key}_flips_per_s": flips[bp_key],
            f"{bp64_key}_flips_per_s": flips[bp64_key],
            f"{bp128_key}_flips_per_s": flips[bp128_key],
            # the multi-word fabric: stacking word planes multiplies the
            # lane count (W=2 -> 64 lanes, W=4 -> 128) with one word loop
            # around the same one-word kernel; lane_efficiency is the
            # per-lane rate at W words over the per-lane rate at one word
            # (the gate: stacking planes must not tax the lanes), measured
            # at the kernel layer with interleaved reps
            "bitplane_word_scaling": word_scaling,
            "speedup_bitplane_vs_int8_R8": flips[bp_key] / flips[i8_key],
            "speedup_bitplane_vs_int8_R8_note": (
                "AGGREGATE lane-flips ratio of one 32-lane word call vs "
                "the R=8 int8 batch (4x the chains per call) — NOT a "
                "per-lane ratio; per_lane_flips_per_s records the "
                "per-chain rates, where int8's small R=8 batch is its "
                "per-replica sweet spot on this host and the matched-"
                "width lane-cost gate is "
                "speedup_bitplane_vs_int8_R32_per_lane"),
            "speedup_bitplane_vs_int8_R32_per_lane":
                flips[bp_key] / flips[f"lattice_fused_int8_R{R_LANES}"],
            "per_lane_flips_per_s": {
                bp_key: flips[bp_key] / R_LANES,
                i8_key: flips[i8_key] / R_BATCH,
                f"lattice_fused_int8_R{R_LANES}":
                    flips[f"lattice_fused_int8_R{R_LANES}"] / R_LANES,
            },
            # the wire format: a face plane ships 4 B/site for ALL 32
            # lanes (uint32 words, the paper's 1 bit per boundary p-bit)
            # vs 1 B/site/replica unpacked int8 planes — 8x smaller at
            # R=32, with zero pack/unpack compute
            "bitplane_halo_payload": {
                "bytes_per_face_site_int8_R32": 32,
                "bytes_per_face_site_bitplane_R32": 4,
                "shrink": 8.0,
            },
            # the same word wire format on the mesh engine: the boundary
            # all-gather ships native uint32 words (4 B/site for all 32
            # chains, zero pack/unpack in the collective chunk) — plus the
            # lane-packed APT+ICM ladder, whose swap moves are lane
            # permutations (cost recorded per move)
            "dsim_dist_bitplane": dist_word,
            "apt_icm_packed": apt_packed,
            # degraded-mode arm: the 2-device mesh under stale_hold with
            # 0/10/30% dropped exchanges — every arm must complete, with
            # effective_eta monotone non-increasing in the drop fraction
            "degraded_mesh": degraded,
            # measured η / f_comm / f_pbit from the EtaMeter probe, the
            # chunk-latency histogram, and the chunk-timer overhead gate
            "telemetry": telemetry,
            "all_paths_flips_per_s": flips,
            # min/median/max + trimmed median sweeps/s over the interleaved
            # reps of each path: a speedup whose intervals overlap is
            # scheduler noise, not signal
            "sweeps_per_s_spread": spread,
        }
        with open(ROOT_BENCH, "w") as f:
            json.dump(bench, f, indent=1, default=float)

    return [row("flip_rate", 1e6 / max(out.get("monolithic",
                                               next(iter(out.values()))),
                                       1e-9),
                " ".join(f"{k}={v:.3e}f/s" for k, v in flips.items()))]
