"""Audit configuration enumeration: every engine x precision x variant.

Builds small fixed problems once, traces each registered configuration
through its ``trace_chunk`` hook over an ``AbstractMesh`` (mesh
collectives appear in the jaxpr without any multi-device backing), and
attaches the declared contracts the IR rules check against:

* predicted collective executions per chunk, derived from the sync_every
  staleness schedule (IR-C);
* the wire payload dtype/bytes from ``boundary_payload()`` (dist) or the
  brick face-plane math (lattice) (IR-B);
* the flat output indices of the chunk-crossing counters (IR-E);
* the ``fused_working_set_bytes`` VMEM model for the lattice (IR-F).

Coverage is driven by ``ENGINE_PRECISIONS`` itself, so registering a new
precision without extending the audit table fails loudly here rather
than silently shrinking the gate.
"""

from __future__ import annotations

from typing import Iterator, List, Tuple

import numpy as np

from .ir_rules import ChunkAudit

__all__ = ["build_audits", "trace_failures"]

# mesh extent along the sharded axis of each toy problem
_K = 2
# chunk shapes: enough iterations that per-iteration vs per-sweep vs
# per-color exchange schedules produce distinct counts
_ITERS, _S = 4, 4


def _problems():
    from repro.compat import abstract_mesh
    from repro.core.coloring import greedy_coloring
    from repro.core.dsim import build_partitioned
    from repro.core.graph import random_regular
    from repro.core.lattice import build_ea3d_lattice
    from repro.core.partition import greedy_partition

    g = random_regular(24, 3, seed=0)
    col = greedy_coloring(np.asarray(g.idx), np.asarray(g.w))
    labels = greedy_partition(np.asarray(g.idx), np.asarray(g.w), _K, seed=0)
    prob = build_partitioned(g, col, np.asarray(labels, np.int32), _K)
    lat = build_ea3d_lattice(8, seed=5)
    return (g, prob, lat,
            abstract_mesh((_K,), ("data",)), abstract_mesh((_K,), ("x",)))


def _dist_payload(eng):
    """(allowed payload dtypes, allowed device-local payload bytes).

    The degraded exchange adds (2,) uint32 integrity headers but ships
    the same payload format as the plain path (boundary_payload()).
    """
    R, b_pad = eng.replicas, eng.b_pad
    if eng.precision == "bitplane":
        return (np.dtype(np.uint32),), (4 * eng.words * b_pad,)
    if eng.mode == "cmft":
        return (np.dtype(np.float32),), (4 * R * b_pad,)
    if eng.bitpack:
        return (np.dtype(np.uint8),), (R * b_pad // 8,)
    return (np.dtype(np.int8),), (R * b_pad,)


def _lattice_payload(eng):
    """Allowed (dtypes, bytes) for every wired face plane of the brick."""
    from repro.core.packing import pad_to_multiple
    bx, by, bz = eng.brick
    faces = {0: by * bz, 1: bx * bz, 2: bx * by}
    wired = [i for i, (a, k) in enumerate(zip(eng.dim_axes, eng.nb))
             if a is not None and k > 1]
    if eng.precision == "bitplane":
        dts: Tuple[np.dtype, ...] = (np.dtype(np.uint32),)
        sizes = tuple(4 * eng.words * faces[i] for i in wired)
    elif eng.bitpack_halos:
        dts = (np.dtype(np.uint8),)
        sizes = tuple(pad_to_multiple(eng.replicas * faces[i], 8) // 8
                      for i in wired)
    else:
        dts = (np.dtype(np.int8),)
        sizes = tuple(eng.replicas * faces[i] for i in wired)
    return dts, tuple(sorted(set(sizes))), len(wired)


def _dist_predict(eng, iters: int, S: int, sync, degrade: bool):
    """Collective executions per chunk from the staleness schedule."""
    if sync == "phase":
        gathers = iters * S * len(eng._consts["color_slots"])
    elif sync is None:
        gathers = 0
    else:
        gathers = iters * S // int(sync)   # one publication per sync sweeps
    if degrade:
        gathers *= 2             # + one (2,) uint32 header per exchange
    out = {"psum": 1}            # final chunk-level energy reduction
    if gathers:
        out["all_gather"] = gathers
    return out


def _lattice_predict(iters: int, n_wired: int, degrade: bool):
    perms = iters * 2 * n_wired  # lo+hi face per wired axis per iteration
    if degrade:
        perms *= 2               # + header ppermute per face exchange
    out = {"psum": 1}
    if perms:
        out["ppermute"] = perms
    if degrade:
        out["pmax"] = 5          # end-of-chunk mesh-wide health consensus
    return out


def _iter_audit_specs() -> Iterator[tuple]:
    """(engine, precision, variant, build kwargs, trace kwargs)."""
    from repro.engines.base import ENGINE_PRECISIONS

    for engine, precisions in ENGINE_PRECISIONS.items():
        for prec in precisions:
            R = 32 if prec == "bitplane" else 1
            base = {"precision": prec, "replicas": R}
            if engine == "gibbs":
                yield engine, prec, "plain", dict(base, rng="lfsr"), {}
            elif engine == "dsim":
                for sync in (4, "phase", None):
                    yield (engine, prec, f"sync={sync}",
                           dict(base, rng="lfsr"), {"sync": sync})
            elif engine == "dsim_dist":
                for sync in (4, "phase", None):
                    yield (engine, prec, f"sync={sync}",
                           dict(base, rng="lfsr"), {"sync": sync})
                yield (engine, prec, "degrade",
                       dict(base, rng="lfsr"), {"sync": 4, "degrade": True})
                yield (engine, prec, "degrade+codes",
                       dict(base, rng="lfsr"),
                       {"sync": 4, "degrade": True, "has_codes": True})
                if prec == "f32":
                    yield (engine, prec, "philox/phase",
                           dict(base, rng="philox"), {"sync": "phase"})
                    yield (engine, prec, "cmft",
                           dict(base, rng="lfsr", mode="cmft"), {"sync": 4})
                    yield (engine, prec, "nobitpack/sync=None",
                           dict(base, rng="lfsr", bitpack=False),
                           {"sync": None})
            else:  # lattice
                yield engine, prec, "plain", dict(base), {}
                yield engine, prec, "degrade", dict(base), {"degrade": True}
                yield (engine, prec, "degrade+codes", dict(base),
                       {"degrade": True, "has_codes": True})


# flat output index of each chunk-crossing counter (register_dataclass
# flattening follows field order; degrade runners append the 6-leaf
# health tuple whose first leaf is the exchange seq counter)
_FLIPS_IDX = {"gibbs": 4, "dsim": 5, "dsim_dist": 5, "lattice": 9}
_STATE_LEAVES = {"dsim_dist": 6, "lattice": 10}


def build_audits() -> Tuple[List[ChunkAudit], List[Tuple[str, str]]]:
    """Trace every configuration; returns (audits, trace failures).

    A configuration that fails to trace is itself a contract violation
    (the audit hooks are part of the engine API) — the runner turns each
    failure into an IR-TRACE finding rather than crashing the gate.
    """
    from repro.engines.registry import make_engine

    g, prob, lat, amesh_d, amesh_x = _problems()
    audits: List[ChunkAudit] = []
    failures: List[Tuple[str, str]] = []

    for engine, prec, variant, mk_kw, tr_kw in _iter_audit_specs():
        loc = f"ir:{engine}/{prec}/{variant}"
        try:
            if engine == "gibbs":
                h = make_engine("gibbs", g, **mk_kw)
            elif engine == "dsim":
                h = make_engine("dsim", prob, **mk_kw)
            elif engine == "dsim_dist":
                h = make_engine("dsim_dist", prob, mesh=amesh_d, **mk_kw)
            else:
                h = make_engine("lattice", lattice=lat, mesh=amesh_x,
                                dim_axes=("x", None, None), impl="ref",
                                **mk_kw)
            traced = h.trace_chunk(_ITERS, _S, **tr_kw)
        except Exception as e:  # noqa: BLE001 — reported, not swallowed
            failures.append((loc, f"{type(e).__name__}: {e}"))
            continue

        eng = h.eng
        degrade = bool(tr_kw.get("degrade"))
        counters = {"flips": _FLIPS_IDX[engine]}
        working_set = None
        if engine in ("gibbs", "dsim"):
            predicted: dict = {}
            dts: Tuple[np.dtype, ...] = ()
            sizes: Tuple[int, ...] = ()
        elif engine == "dsim_dist":
            predicted = _dist_predict(eng, _ITERS, _S, tr_kw.get("sync"),
                                      degrade)
            dts, sizes = _dist_payload(eng)
        else:
            dts, sizes, n_wired = _lattice_payload(eng)
            predicted = _lattice_predict(_ITERS, n_wired, degrade)
            from repro.core.lattice_dsim import fused_working_set_bytes
            working_set = (
                fused_working_set_bytes(
                    eng.brick, lat.n_colors, precision=prec,
                    lanes=eng.replicas, tiled=False),
                tuple(eng.brick))
        if degrade:
            counters["seq"] = _STATE_LEAVES[engine]

        audits.append(ChunkAudit(
            engine=engine, precision=prec, variant=variant,
            closed=traced.jaxpr, predicted=predicted,
            payload_dtypes=dts, payload_bytes=sizes,
            counters=counters, working_set=working_set))
    return audits, failures


def trace_failures(failures) -> list:
    from .findings import Finding
    return [Finding(
        "IR-TRACE", loc,
        f"configuration failed to trace: {msg}",
        "trace_chunk over an AbstractMesh is part of the engine audit "
        "API — fix the hook or the engine") for loc, msg in failures]
