#!/usr/bin/env python3
"""On-chip smoke test: the p-bit machine's main path, end to end, on TPU.

    python chip_smoke.py             # one chip: phases (b)-(e)
    python chip_smoke.py --chips 4   # four chips: the partitioned machine

One chip runs the paper's 10^6-p-bit EA3D spin glass (L=100) through the
user entry points — ``repro.engines.make_engine`` and
``repro.serve.SampleServer`` — and checks every phase against the repo's
own references:

  (b) int8 lattice, 8 replicas: Pallas kernel path, recorded energy equal
      to the energy recomputed on the host from ``global_spins``, and the
      first chunk's spins bitwise equal to the ``impl="ref"`` oracle;
  (c) f32 lattice: energy falls and matches the host recomputation; the
      share of sites that differ from ``impl="ref"`` is reported (tanh on
      the chip need not round like XLA's);
  (d) bitplane lattice, 32 lanes, at the largest brick under its VMEM
      ceiling: lane r bitwise equal to int8 replica r;
  (e) serving: six lattice jobs with mixed replica counts, packed by the
      scheduler, all done with finite energies.

``--chips 4`` runs only what exists across chips: ``dsim_dist`` int8 on a
4-device slab mesh checked bitwise against the stacked one-chip ``dsim``
engine, and the L=100 int8 lattice brick-partitioned over x on 4 devices
(shards on 4 distinct devices, energy checks).  Both print measured eta
against ``commcost.eta_threshold`` as information.

The script refuses to run anywhere but a TPU.  Every phase prints its
timings on lines of its own; the last line is one JSON object,
``{"ok": true, "device": {...}}``.  Any failed check raises and exits
non-zero.  The compile cache is placed by ``repro.cache``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
SEED = 7


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)
    log(f"  ok: {what}")


def host_energy(L: int, seed: int, spins):
    """Ising energy -sum_ij J_ij m_i m_j of each replica's (L^3,) spins,
    recomputed on the host (float64) from the EA3D edge list."""
    import numpy as np
    from repro.core.graph import ea3d_edges
    ei, ej, ew = ea3d_edges(L, seed)
    ew = np.asarray(ew, np.float64)
    spins = np.atleast_2d(np.asarray(spins))
    return np.array([-(ew * s[ei] * s[ej]).sum()
                     for s in spins.astype(np.float64)])


def _points(sweeps: int, sync: int):
    return list(range(sync, sweeps + 1, sync))


def _run(h, st, sweeps: int, sync: int):
    """One recorded anneal; returns (state, record, wall seconds)."""
    import jax
    from repro.core.annealing import ea_schedule
    t0 = time.perf_counter()
    st, rec = h.run_recorded(st, ea_schedule(sweeps), _points(sweeps, sync),
                             sync_every=sync)
    jax.block_until_ready(st)
    return st, rec, time.perf_counter() - t0


def _timed(label: str, h, seed: int, sweeps: int, sync: int):
    """Cold run (compiles) then a warm run of the same anneal."""
    st, rec, cold = _run(h, h.init_state(seed=seed), sweeps, sync)
    st, rec, warm = _run(h, h.init_state(seed=seed), sweeps, sync)
    flips = int(rec.flips)
    log(f"  time {label}: cold {cold:.3f} s (compile included), "
        f"warm {warm:.3f} s")
    log(f"  rate {label}: {flips} flips in {warm:.3f} s = "
        f"{flips / warm:.6g} flips/s")
    return st, rec


def _energy_check(h, st, rec, L: int, seed: int, label: str) -> None:
    import numpy as np
    e_rec = np.asarray(rec.energies[-1], np.float64)
    e_host = host_energy(L, seed, h.global_spins(st))
    log(f"  {label} energy: recorded {e_rec.tolist()} host {e_host.tolist()}")
    check(np.array_equal(e_rec, e_host),
          f"{label}: recorded energy == host recomputation from "
          f"global_spins")


def _resolved_impl(h) -> str:
    """The kernel implementation the engine runs ("pallas" on a TPU)."""
    from repro.kernels.ops import default_impl
    return h.eng.impl if h.eng.impl != "auto" else default_impl()


def phase_int8(L: int, impl: str, sweeps: int = 16, sync: int = 4) -> None:
    import numpy as np
    from repro.core.lattice import build_ea3d_lattice
    from repro.engines import make_engine
    log(f"phase b: int8 lattice L={L}, 8 replicas")
    prob = build_ea3d_lattice(L, seed=SEED)
    h = make_engine("lattice", lattice=prob, precision="int8", replicas=8,
                    impl=impl)
    log(f"  kernel_path={h.kernel_path} "
        f"fallback_reason={h.eng.fallback_reason} "
        f"kernel_bx={h.eng.kernel_bx} energy_bx={h.eng.energy_bx} "
        f"impl={_resolved_impl(h)}")
    check(_resolved_impl(h) != "ref"
          and h.kernel_path in ("fused", "per_phase"),
          "int8 lattice runs a Pallas kernel path")
    st, rec = _timed("b", h, SEED, sweeps, sync)
    e = np.asarray(rec.energies)
    check(bool(np.isfinite(e).all()), "recorded energies finite")
    _energy_check(h, st, rec, L, SEED, "int8")
    # first chunk, Pallas vs the jnp oracle, same chip and seed
    ref = make_engine("lattice", lattice=prob, precision="int8", replicas=8,
                      impl="ref")
    a, _, _ = _run(h, h.init_state(seed=SEED), sync, sync)
    b, _, _ = _run(ref, ref.init_state(seed=SEED), sync, sync)
    check(bool((np.asarray(h.global_spins(a))
                == np.asarray(ref.global_spins(b))).all()),
          "first chunk spins bitwise equal to impl='ref'")


def phase_f32(L: int, impl: str, sweeps: int = 16, sync: int = 4) -> None:
    import numpy as np
    from repro.core.lattice import build_ea3d_lattice
    from repro.engines import make_engine
    log(f"phase c: f32 lattice L={L}, 2 replicas")
    prob = build_ea3d_lattice(L, seed=SEED)
    h = make_engine("lattice", lattice=prob, replicas=2, impl=impl)
    log(f"  kernel_path={h.kernel_path} "
        f"fallback_reason={h.eng.fallback_reason} "
        f"kernel_bx={h.eng.kernel_bx} impl={_resolved_impl(h)}")
    check(_resolved_impl(h) != "ref", "f32 lattice runs a Pallas kernel path")
    e0 = np.asarray(h.energy(h.init_state(seed=SEED)), np.float64)
    st, rec = _timed("c", h, SEED, sweeps, sync)
    e1 = np.asarray(rec.energies[-1], np.float64)
    log(f"  f32 energy: initial {e0.tolist()} final {e1.tolist()}")
    check(bool((e1 < e0).all()), "f32 energy falls")
    _energy_check(h, st, rec, L, SEED, "f32")
    ref = make_engine("lattice", lattice=prob, replicas=2, impl="ref")
    b, _, _ = _run(ref, ref.init_state(seed=SEED), sweeps, sync)
    diff = float((np.asarray(h.global_spins(st))
                  != np.asarray(ref.global_spins(b))).mean())
    log(f"  f32 sites differing from impl='ref' after {sweeps} sweeps: "
        f"{diff:.6g}")


def phase_bitplane(impl: str, sweeps: int = 16, sync: int = 4,
                   L=None) -> None:
    import numpy as np
    from repro.core.lattice import build_ea3d_lattice
    from repro.core.lattice_dsim import fused_brick_ceiling
    from repro.engines import make_engine
    if L is None:
        L = fused_brick_ceiling(2, "bitplane", lanes=32)
        L -= L % 2                     # even L: the 2-color lattice
    log(f"phase d: bitplane lattice L={L} (ceiling brick), 32 lanes")
    prob = build_ea3d_lattice(L, seed=SEED)
    check(prob.n_colors == 2, "ceiling lattice is 2-colored")
    bp = make_engine("lattice", lattice=prob, precision="bitplane",
                     replicas=32, impl=impl)
    i8 = make_engine("lattice", lattice=prob, precision="int8",
                     replicas=32, impl=impl)
    log(f"  kernel_path={bp.kernel_path} working_set="
        f"{bp.eng.fused_working_set} B impl={_resolved_impl(bp)}")
    check(_resolved_impl(bp) != "ref",
          "bitplane lattice runs the Pallas kernel")
    a, ra = _timed("d bitplane", bp, SEED, sweeps, sync)
    b, rb = _timed("d int8x32", i8, SEED, sweeps, sync)
    check(bool((np.asarray(bp.global_spins(a))
                == np.asarray(i8.global_spins(b))).all()),
          "bitplane lane r bitwise equal to int8 replica r (32 lanes)")
    check(bool(np.array_equal(np.asarray(ra.energies),
                              np.asarray(rb.energies))),
          "bitplane and int8 energy traces equal")


def phase_serve(L: int, sweeps: int = 8, sync: int = 4) -> None:
    import numpy as np
    from repro.serve import SampleServer
    log(f"phase e: SampleServer, lattice L={L}, 6 int8 jobs")
    srv = SampleServer(max_replicas_per_call=8, stream_chunks=2)
    srv.register_problem("ea3d", L=L, seed=SEED)
    reps = [1, 2, 1, 3, 1, 2]
    t0 = time.perf_counter()
    jobs = [srv.submit("ea3d", engine="lattice", precision="int8",
                       sweeps=sweeps, sync_every=sync, replicas=r, seed=k)
            for k, r in enumerate(reps)]
    srv.start()
    try:
        results = [srv.result(j, timeout=900) for j in jobs]
    finally:
        srv.stop()
    wall = time.perf_counter() - t0
    for j, r, res in zip(jobs, reps, results):
        e = np.asarray(res["energies"], np.float64)
        log(f"  {j}: R={r} status={res['status']} best_energy="
            f"{res['best_energy']} packed_with={res.get('packed_with')}")
        check(res["status"] == "done", f"{j} done")
        check(e.size > 0 and bool(np.isfinite(e).all()),
              f"{j} energies finite")
    st = srv.stats()
    log(f"  scheduler: {json.dumps(st['scheduler'], default=str)}")
    log(f"  pool: {json.dumps(st['pool'], default=str)}")
    log(f"  time e: {len(jobs)} jobs in {wall:.3f} s "
        f"({st['engine_calls']} engine calls, compile included)")
    check(st["scheduler"]["jobs_packed"] > 0, "the scheduler packed jobs")


def _eta_line(meter, label: str) -> None:
    rep = meter.report()
    log(f"  eta {label}: measured {rep['measured_eta']:.6g} vs threshold "
        f"{rep['eta_threshold']:.6g} (margin {rep['margin']:.6g}, "
        f"information only)")


def phase_dist4(L: int, sweeps: int = 16, sync: int = 4) -> None:
    import numpy as np
    from repro.compat import auto_axes, make_mesh
    from repro.core.annealing import ea_schedule
    from repro.core.coloring import lattice3d_coloring
    from repro.core.dsim import build_partitioned
    from repro.core.graph import ea3d
    from repro.core.partition import slab_partition
    from repro.engines import make_engine
    from repro.obs import dist_eta_meter
    log(f"phase 4a: dsim_dist int8, K=4 slab partition of EA3D L={L}, "
        f"2 replicas")
    t0 = time.perf_counter()
    g = ea3d(L, seed=SEED)
    prob = build_partitioned(g, lattice3d_coloring(L), slab_partition(L, 4),
                             4)
    build = time.perf_counter() - t0
    log(f"  time 4a host build: {build:.3f} s")
    check(build < 60.0, "host-side build under a minute")
    mesh = make_mesh((4,), ("data",), axis_types=auto_axes(1))
    # R=2: both engines seed replica r from spawn_seeds(seed, R)[r]
    hd = make_engine("dsim_dist", prob, mesh=mesh, rng="lfsr",
                     precision="int8", replicas=2)
    hs = make_engine("dsim", prob, rng="lfsr", precision="int8", replicas=2)
    sd, rd = _timed("4a dsim_dist", hd, SEED, sweeps, sync)
    ss, rs = _timed("4a dsim stacked", hs, SEED, sweeps, sync)
    check(len({s.device for s in sd.m.addressable_shards}) == 4,
          "dsim_dist state sharded over 4 distinct devices")
    check(bool((np.asarray(hd.global_spins(sd))
                == np.asarray(hs.global_spins(ss))).all()),
          "dsim_dist spins bitwise equal to the stacked dsim engine")
    check(bool(np.array_equal(np.asarray(rd.energies),
                              np.asarray(rs.energies))),
          "dsim_dist energy trace equal to the stacked engine's")
    meter = dist_eta_meter(hd.eng, sync_every=sync)
    cur = hd.start_recorded(hd.init_state(seed=SEED), ea_schedule(sweeps),
                            _points(sweeps, sync), sync_every=sync)
    meter.attach(cur)
    while not cur.done:
        cur.advance(1)
    st = hd.init_state(seed=SEED)
    meter.measure_exchange(lambda: hd.eng.boundary_exchange_fn()(st))
    _eta_line(meter, "4a")


def phase_lattice4(L: int, impl: str, sweeps: int = 16,
                   sync: int = 4) -> None:
    import numpy as np
    from repro.compat import auto_axes, make_mesh
    from repro.core import commcost
    from repro.core.annealing import ea_schedule
    from repro.core.graph import ea3d
    from repro.core.lattice import build_ea3d_lattice
    from repro.core.partition import slab_partition
    from repro.engines import make_engine
    from repro.obs import EtaMeter
    log(f"phase 4b: int8 lattice L={L} brick-partitioned over x on 4 "
        f"devices, 8 replicas")
    prob = build_ea3d_lattice(L, seed=SEED)
    mesh = make_mesh((4,), ("x",), axis_types=auto_axes(1))
    h = make_engine("lattice", lattice=prob, mesh=mesh,
                    dim_axes=("x", None, None), precision="int8",
                    replicas=8, impl=impl)
    log(f"  brick={h.eng.brick} kernel_path={h.kernel_path} "
        f"fallback_reason={h.eng.fallback_reason} impl={_resolved_impl(h)}")
    check(_resolved_impl(h) != "ref",
          "4-device lattice runs a Pallas kernel path")
    st0 = h.init_state(seed=SEED)
    shards = st0.m.addressable_shards
    devs = {s.device for s in shards}
    log(f"  shards: {[(str(s.device), s.data.shape) for s in shards]}")
    check(len(devs) == 4, "lattice state shards sit on 4 distinct devices")
    check(sorted(s.index[1].start or 0 for s in shards)
          == [k * (L // 4) for k in range(4)],
          "each device holds its own x-slab")
    e0 = np.asarray(h.energy(st0), np.float64)
    st, rec = _timed("4b", h, SEED, sweeps, sync)
    e = np.asarray(rec.energies, np.float64)
    log(f"  residual energy: initial {e0.tolist()} per point "
        f"{e.mean(axis=1).tolist()}")
    check(bool((e[-1] < e0).all() and e[-1].mean() <= e[0].mean()),
          "residual energy falls")
    _energy_check(h, st, rec, L, SEED, "4-device int8")
    g = ea3d(L, seed=SEED)
    b = commcost.boundary_matrix(np.asarray(g.idx), np.asarray(g.w),
                                 slab_partition(L, 4), 4)
    c_max = commcost.comm_cost(b, commcost.RingTopology(
        k=4, pins_per_link=1)).c_max
    meter = EtaMeter(n_color=prob.n_colors, c_max=c_max, sync_every=sync)
    cur = h.start_recorded(h.init_state(seed=SEED), ea_schedule(sweeps),
                           _points(sweeps, sync), sync_every=sync)
    meter.attach(cur)
    while not cur.done:
        cur.advance(1)
    meter.measure_exchange(lambda: h.eng.boundary_exchange_fn()(st))
    _eta_line(meter, "4b")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases (b)-(e) on one chip; 4: only the "
                         "multi-chip phases")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        log(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}; "
            f"refusing to fall back")
        return 2
    if len(devs) < args.chips:
        log(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
            f"JAX found {len(devs)}")
        return 2
    sys.path.insert(0, SRC)
    from repro.cache import enable_compile_cache
    cache = enable_compile_cache()
    n_cached = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    log(f"device: {dev.platform} {dev.device_kind} x{len(devs)}; "
        f"jax {jax.__version__}; compile cache {cache} "
        f"({n_cached} entries at start)")

    t0 = time.perf_counter()
    if args.chips == 4:
        phase_dist4(100)
        phase_lattice4(100, "auto")
    else:
        phase_int8(100, "auto")
        phase_f32(100, "auto")
        phase_bitplane("auto")
        phase_serve(100)
    n_cached = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    log(f"all phases passed in {time.perf_counter() - t0:.3f} s; compile "
        f"cache {cache} holds {n_cached} entries")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
