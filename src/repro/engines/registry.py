"""String-keyed engine factory and the uniform adapters behind it.

``make_engine(name, ...)`` builds any of the four sampling backends from a
problem description and returns a handle satisfying the :class:`Engine`
protocol: replicated ``init_state``, driver-backed ``run_recorded`` with
(P, R) per-replica energy traces and exact flip totals, ``energy``,
``global_spins``, and ``lower_chunk``.

At replicas=1 every handle is bitwise identical to its legacy class driven
directly (same seeds, same RNG streams) — the adapters only normalize
shapes, never dynamics.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax.numpy as jnp
import numpy as np

from repro.core.graph import IsingGraph
from repro.core.coloring import Coloring, greedy_coloring
from repro.core.gibbs import GibbsEngine
from repro.core.dsim import PartitionedProblem, build_partitioned, DSIMEngine
from repro.core.dsim_dist import DistDSIMEngine
from repro.core.lattice import LatticeProblem, build_ea3d_lattice
from repro.core.lattice_dsim import LatticeDSIM
from repro.compat import make_mesh, auto_axes
from repro.core.snapshot import restore_state, snapshot_state
from .base import RunRecord, SyncSpec, check_lanes, check_precision

__all__ = ["ENGINE_NAMES", "make_engine", "HandleCursor"]

ENGINE_NAMES = ("gibbs", "dsim", "dsim_dist", "lattice")


def _as_2d(energies: jnp.ndarray) -> jnp.ndarray:
    """(P,) single-replica trace -> (P, 1); (P, R) passes through."""
    return energies[:, None] if energies.ndim == 1 else energies


def _as_1d(x) -> jnp.ndarray:
    return jnp.atleast_1d(jnp.asarray(x))


class HandleCursor:
    """Registry-normalized view of a :class:`RecordedCursor`.

    Same incremental surface (``advance``/``done``/``record``), but partial
    records come back in handle shape — energies always (P, R) — and the
    per-counter flip totals are reduced to one exact total per replica, so
    a packing scheduler can attribute flips to the replica slices of the
    jobs it coalesced into this one batched run.
    """

    def __init__(self, cursor, replicas: int, handle=None):
        self._c = cursor
        self.replicas = int(replicas)
        self._handle = handle

    @property
    def state(self):
        return self._c.state

    @state.setter
    def state(self, st):
        # fault injection ("corrupt" rules) swaps the live state in place
        self._c.state = st

    @property
    def fault_hook(self):
        """Per-chunk boundary hook on the underlying cursor (fault
        injection fires here, at the boundary-exchange points)."""
        return self._c.fault_hook

    @fault_hook.setter
    def fault_hook(self, fn):
        self._c.fault_hook = fn

    @property
    def chunk_timer(self):
        """Per-chunk `(sweeps, seconds)` timer on the underlying cursor
        (telemetry: obs.EtaMeter / server pump-latency attach here)."""
        return self._c.chunk_timer

    @chunk_timer.setter
    def chunk_timer(self, fn):
        self._c.chunk_timer = fn

    @property
    def done(self) -> bool:
        return self._c.done

    @property
    def sweeps_done(self) -> int:
        return self._c.sweeps_done

    @property
    def total_sweeps(self) -> int:
        return self._c.total_sweeps

    @property
    def S(self) -> int:
        """The record-point quantum the cursor actually applied (1 for
        engines without boundaries, whatever sync_every resolved to
        otherwise) — callers mirroring the quantization must use this,
        not the sync_every they passed in."""
        return self._c.S

    @property
    def points_recorded(self) -> int:
        return self._c.points_recorded

    @property
    def flips(self) -> int:
        return self._c.flips

    def advance(self, max_chunks: int = 1) -> int:
        n = self._c.advance(max_chunks)
        if self._c.done:
            self._c.run_to_completion()    # settles the pending flip window
        return n

    def record(self) -> RunRecord:
        rec = self._c.record()
        e = rec.energies
        if len(rec.times) > 0:
            e = _as_2d(e)
        return RunRecord(rec.times, e, rec.flips)

    def flips_per_replica(self) -> np.ndarray:
        """(R,) exact per-replica flip totals up to the last record or
        bound point (settles the cursor's pending snapshots)."""
        vec = self._c.flips_vec
        if vec is None:
            return np.zeros((self.replicas,), np.int64)
        if vec.shape[0] == self.replicas:
            return vec.reshape(self.replicas, -1).sum(axis=1)
        if self.replicas == 1:
            return np.asarray([vec.sum()], np.int64)
        raise ValueError(
            f"flip counters {vec.shape} don't lead with R={self.replicas}")

    def warm(self):
        self._c.warm()
        return self

    def checkpoint(self) -> dict:
        """Picklable mid-run checkpoint (state pulled to host via the
        handle's ``snapshot`` when the cursor was built by one)."""
        fn = self._handle.snapshot if self._handle is not None else None
        return self._c.checkpoint(snapshot_fn=fn)

    def restore_checkpoint(self, ck: dict):
        """Resume from :meth:`checkpoint` output, bitwise-identically;
        the state is pushed back to device (re-sharded) via the handle's
        ``restore``.  Raises ValueError on a plan mismatch."""
        fn = self._handle.restore if self._handle is not None else None
        self._c.restore_checkpoint(ck, restore_fn=fn)
        return self


class _Handle:
    """Shared adapter plumbing over a legacy engine instance.

    The default methods cover the engines whose replicas are fixed at
    construction (dist, lattice); the batched-state engines (gibbs, dsim)
    override ``init_state`` to thread the replica count, and gibbs alone
    overrides ``_recorded`` (it has no boundaries, so no sync_every)."""

    name: str = ""
    supports_packing: bool = True     # init_state_packed(seeds) available

    def __init__(self, eng, replicas: int, n_sites: int):
        self.eng = eng
        self.replicas = int(replicas)
        self.n_sites = int(n_sites)

    @property
    def precision(self) -> str:
        """Numeric pipeline of the update rule ("f32" or "int8")."""
        return getattr(self.eng, "precision", "f32")

    @property
    def kernel_path(self):
        """Which lattice dispatch actually runs ("fused"/"per_phase");
        None for engines without the fused/per-phase split."""
        return getattr(self.eng, "kernel_path", None)

    def init_state(self, seed: int = 0):
        return self.eng.init_state(seed)

    def init_state_packed(self, seeds: Sequence[int]):
        """Batched state whose replica r is seeded by seeds[r] alone —
        the replica-packing path: R == len(seeds) must match the handle,
        and each chain's trajectory is independent of its batch-mates."""
        seeds = [int(s) for s in seeds]
        if len(seeds) != self.replicas:
            raise ValueError(
                f"need exactly R={self.replicas} seeds, got {len(seeds)}")
        return self.eng.init_state(seeds=seeds)

    def _recorded(self, state, schedule, record_points, sync_every, cursor):
        return self.eng.run_recorded_full(state, schedule, record_points,
                                          sync_every=sync_every,
                                          cursor=cursor)

    def run_recorded(self, state, schedule, record_points: Sequence[int],
                     sync_every: SyncSpec = 1):
        state, rec = self._recorded(state, schedule, record_points,
                                    sync_every, cursor=False)
        return state, RunRecord(rec.times, _as_2d(rec.energies), rec.flips)

    def start_recorded(self, state, schedule, record_points: Sequence[int],
                       sync_every: SyncSpec = 1) -> HandleCursor:
        """Begin (not run) a recorded anneal; returns a resumable
        :class:`HandleCursor` advanced chunk by chunk by the caller."""
        cur = self._recorded(state, schedule, record_points, sync_every,
                             cursor=True)
        return HandleCursor(cur, self.replicas, handle=self)

    def snapshot(self, state):
        """Host-side owned copy of an engine state (see core.snapshot)."""
        return snapshot_state(state)

    def restore(self, snap):
        """Snapshot -> live device state, re-sharded where the engine
        shards (lattice, dist)."""
        st = restore_state(snap)
        if hasattr(self.eng, "shard_state"):
            st = self.eng.shard_state(st)
        return st

    def energy(self, state) -> jnp.ndarray:
        return _as_1d(self.eng.energy(state))

    def global_spins(self, state) -> jnp.ndarray:
        return jnp.atleast_2d(self.eng.global_spins(state))

    def lower_chunk(self, iters: int = 2, S: int = 4):
        return self.eng.lower_chunk(iters=iters, S=S)

    def trace_chunk(self, iters: int = 2, S: int = 4, **kw):
        """Traced (not lowered) chunk for the static contract auditor:
        returns the jitted runner's Traced object.  Mesh engines accept
        ``sync=``/``degrade=``/``freeze=``/``has_codes=`` passthroughs and
        trace over ``AbstractMesh`` without any device backing."""
        return self.eng.trace_chunk(iters=iters, S=S, **kw)

    def __repr__(self):
        return (f"<engine {self.name!r} n={self.n_sites} "
                f"R={self.replicas}>")


class _BatchedStateHandle(_Handle):
    """gibbs/dsim: the replica axis lives on the state, not the engine."""

    def init_state(self, seed: int = 0):
        # R=1 keeps the legacy unbatched state (bitwise-stable trajectories)
        return self.eng.init_state(
            seed, replicas=None if self.replicas == 1 else self.replicas)


class _GibbsHandle(_BatchedStateHandle):
    name = "gibbs"

    def _recorded(self, state, schedule, record_points, sync_every, cursor):
        # monolithic: no boundaries, so no sync_every
        return self.eng.run_recorded_full(state, schedule, record_points,
                                          cursor=cursor)

    def energy(self, state) -> jnp.ndarray:
        return _as_1d(self.eng.direct_energy(state))

    def global_spins(self, state) -> jnp.ndarray:
        return jnp.atleast_2d(state.m)

    def _chunk_fn_args(self, iters: int, S: int):
        st = self.init_state(seed=0)
        batched = self.eng.is_batched(st)
        betas = jnp.zeros((iters * S,), jnp.float32)
        return self.eng._run_chunk(iters * S, batched), (st, betas)

    def lower_chunk(self, iters: int = 2, S: int = 4):
        run, args = self._chunk_fn_args(iters, S)
        return run.lower(*args)

    def trace_chunk(self, iters: int = 2, S: int = 4, **kw):
        run, args = self._chunk_fn_args(iters, S)
        return run.trace(*args)


class _DSIMHandle(_BatchedStateHandle):
    name = "dsim"

    def _chunk_fn_args(self, iters: int, S: int, sync: SyncSpec = None):
        st = self.init_state(seed=0)
        batched = self.eng.is_batched(st)
        sync = S if sync is None else sync
        if self.eng.precision == "int8":
            from repro.core.annealing import beta_table
            table = beta_table(np.ones((iters * S,), np.float32))
            lut = self.eng._lut_for(table)
            rows = jnp.zeros((iters, S), jnp.int32)
            return self.eng._run_chunk(iters, S, sync, batched), \
                (st, rows, lut)
        betas = jnp.zeros((iters, S), jnp.float32)
        return self.eng._run_chunk(iters, S, sync, batched), (st, betas)

    def lower_chunk(self, iters: int = 2, S: int = 4):
        run, args = self._chunk_fn_args(iters, S, S)
        return run.lower(*args)

    def trace_chunk(self, iters: int = 2, S: int = 4, sync: SyncSpec = None,
                    **kw):
        run, args = self._chunk_fn_args(iters, S, sync)
        return run.trace(*args)


class _DistHandle(_Handle):
    name = "dsim_dist"
    # the mesh engine's f32 path derives all replica RNG streams jointly
    # from one seed; the int8/bitplane paths spawn per-replica streams
    # (prefix-stable lanes) but the handle still runs one tenant per call —
    # the serving scheduler never packs dist jobs, so per-job seed lists
    # are not exposed here
    supports_packing = False

    def init_state_packed(self, seeds: Sequence[int]):
        raise NotImplementedError(
            "dsim_dist runs one tenant per batched call (no replica "
            "packing); submit with replicas=R and a single seed instead")


class _LatticeHandle(_Handle):
    name = "lattice"


def _default_coloring(g: IsingGraph, coloring: Optional[Coloring]) -> Coloring:
    if coloring is not None:
        return coloring
    return greedy_coloring(np.asarray(g.idx), np.asarray(g.w))


def _default_partitioned(graph, coloring, K, labels) -> PartitionedProblem:
    if isinstance(graph, PartitionedProblem):
        return graph
    g = graph
    col = _default_coloring(g, coloring)
    K = 4 if K is None else int(K)
    if labels is None:
        from repro.core.partition import greedy_partition
        labels = greedy_partition(np.asarray(g.idx), np.asarray(g.w), K,
                                  seed=0)
    return build_partitioned(g, col, np.asarray(labels, np.int32), K)


def make_engine(name: str, graph=None, *, coloring: Optional[Coloring] = None,
                replicas: int = 1, rng: str = "philox", fmt=None,
                K: Optional[int] = None, labels=None, mode: str = "dsim",
                mesh=None, axis: str = "data", dim_axes=None,
                lattice: Optional[LatticeProblem] = None,
                L: Optional[int] = None, seed: int = 0,
                impl: str = "auto", bitpack: bool = True,
                fused: bool = True, kernel_bx: Optional[int] = None,
                bitpack_halos: bool = True, precision: str = "f32",
                vmem_budget_bytes: Optional[int] = None,
                degrade=None):
    """Build a sampling engine by name.

      "gibbs"     — monolithic chromatic Gibbs; needs ``graph`` (+coloring).
      "dsim"      — partitioned, stacked on one device; ``graph`` (or a
                    prebuilt PartitionedProblem) + K/labels.
      "dsim_dist" — the same semantics across a device mesh; K must equal
                    the mesh axis size (defaults to a mesh over all local
                    devices).
      "lattice"   — brick-partitioned structured EA3D lattice (the fused-
                    kernel production path); pass ``lattice=`` a
                    LatticeProblem or ``L=`` to build one from ``seed``.

    ``replicas=R`` makes every handle run R independent chains per call.

    ``precision="int8"`` selects the fixed-point update pipeline (int8
    on-chip couplings, integer field accumulation, LUT-threshold accepts)
    on the dsim, dsim_dist, and lattice engines; ``precision="bitplane"``
    (lattice and dsim_dist) multi-spin-codes that pipeline — spins stored
    as uint32 bit-planes with up to 32 replica lanes per word, word-wide
    field math, per-lane RNG; lane r is bit-identical to int8 replica r.
    On dsim_dist the boundary all-gather ships the native words (4 B per
    boundary site for all 32 chains, zero pack/unpack on the collective
    path).  ``"f32"`` (default) is the floating reference the integer
    paths are statistically compared against.

    ``degrade=`` (mesh engines only) turns on the boundary-integrity
    layer with a ``core.degrade.DegradePolicy`` — None, a policy object,
    or "fail_fast" | "stale_hold[:N]" | "freeze_boundary".
    """
    if name not in ENGINE_NAMES:
        raise ValueError(f"unknown engine {name!r}; choose from {ENGINE_NAMES}")
    if replicas < 1:
        raise ValueError("replicas must be >= 1")
    check_precision(name, precision)
    check_lanes(precision, replicas)
    if degrade is not None and name not in ("dsim_dist", "lattice"):
        raise ValueError(
            f"degrade policies apply to the mesh engines "
            f"(dsim_dist, lattice), not {name!r}")

    if name == "gibbs":
        if not isinstance(graph, IsingGraph):
            raise ValueError("gibbs engine needs an IsingGraph")
        eng = GibbsEngine(graph, _default_coloring(graph, coloring),
                          rng=rng, fmt=fmt)
        return _GibbsHandle(eng, replicas, graph.n)

    if name == "dsim":
        prob = _default_partitioned(graph, coloring, K, labels)
        eng = DSIMEngine(prob, rng=rng, fmt=fmt, mode=mode,
                         precision=precision)
        return _DSIMHandle(eng, replicas, prob.n)

    if name == "dsim_dist":
        prob = _default_partitioned(graph, coloring, K, labels)
        if mesh is None:
            import jax
            ndev = len(jax.devices())
            if ndev != prob.K:
                raise ValueError(
                    f"dsim_dist needs a mesh with K={prob.K} devices along "
                    f"{axis!r} (have {ndev}); pass mesh= explicitly")
            mesh = make_mesh((prob.K,), (axis,), axis_types=auto_axes(1))
        eng = DistDSIMEngine(prob, mesh, axis=axis, rng=rng, fmt=fmt,
                             mode=mode, bitpack=bitpack, replicas=replicas,
                             precision=precision, degrade=degrade)
        return _DistHandle(eng, replicas, prob.n)

    # name == "lattice"
    prob = lattice
    if prob is None:
        if L is None:
            raise ValueError("lattice engine needs lattice= or L=")
        prob = build_ea3d_lattice(int(L), seed=seed)
    if mesh is None:
        mesh = make_mesh((1,), (axis,), axis_types=auto_axes(1))
        dim_axes = (axis, None, None) if dim_axes is None else dim_axes
    elif dim_axes is None:
        raise ValueError("pass dim_axes when passing a mesh")
    extra = {} if vmem_budget_bytes is None else \
        {"vmem_budget_bytes": vmem_budget_bytes}
    eng = LatticeDSIM(prob, mesh, dim_axes=dim_axes, fmt=fmt, impl=impl,
                      kernel_bx=kernel_bx, bitpack_halos=bitpack_halos,
                      fused=fused, replicas=replicas, precision=precision,
                      degrade=degrade, **extra)
    return _LatticeHandle(eng, replicas, prob.n_active)
