"""Engine protocol, chunk planning, and the shared recording driver.

Before this layer, every backend hand-rolled the same ``run_recorded`` loop
(quantize record points to exchange boundaries, decompose the gaps into
power-of-two chunks, jit one runner per chunk length, read an observable at
each record point).  The four near-duplicates now all call
:func:`run_recorded_driver`; a backend only supplies its chunk runner and
its observable.

Flip accounting: device-side counters are int32 (TPU-native), which wraps
after ~2.1e9 flips — minutes of runtime at the paper's 1e12 flips/s.  The
driver therefore treats the device counter as a modular odometer.  At each
record point, and before the worst-case flips since the last snapshot
could reach 2**31, it takes a device-side snapshot of the counter (its
host copy started, nothing waited on).  Only when a caller asks for flips
does it settle: one host read of every pending snapshot, folded in order
as deltas mod 2**32 into the exact total, a host-side Python int
(arbitrary precision, so >= int64 by construction).  Each delta spans two
consecutive snapshots, which the bound keeps below 2**31 worst-case flips
apart, so it is unambiguous however many snapshots one settle folds;
``chunk_plan(max_chunk=...)`` keeps a single chunk below that bound too.
The host runs at most two chunks ahead of the device: before it
dispatches a third, it waits on the oldest one's flip counter.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Callable, List, Optional, Protocol, Sequence, Union, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Engine", "RunRecord", "SyncSpec", "chunk_plan",
           "run_recorded_driver", "RecordedCursor", "spawn_seeds",
           "stack_states", "flips_chunk_cap", "PRECISIONS",
           "ENGINE_PRECISIONS", "LANE_WIDTH", "MAX_LANE_WORDS", "lane_words",
           "lanes_of", "check_precision", "check_lanes"]

SyncSpec = Union[int, str, None]

# ---------------------------------------------------------------------------
# precision pipelines
# ---------------------------------------------------------------------------
#
# "f32"      — floating reference (tanh + float compare, Philox or LFSR).
# "int8"     — the hardware's fixed-point pipeline: int8 on-chip couplings,
#              integer field accumulation, LUT-threshold accepts.
# "bitplane" — multi-spin coding over the int8 substrate: spins as uint32
#              bit-planes, 32 replica lanes per word, stacked into W word
#              planes (lane l = word l//32, bit l%32), word-wide field math
#              with per-lane RNG/accept.  Lattice engine (halo planes) and
#              mesh engine (native-word boundary all-gather); replicas are
#              lanes, so R <= MAX_LANE_WORDS * LANE_WIDTH.
#
# One shared table so the registry, the serving layer, and the engines all
# reject an unsupported (engine, precision) pair with the same clear error
# — a scheduler-level shape error is never the first symptom.

PRECISIONS = ("f32", "int8", "bitplane")
ENGINE_PRECISIONS = {
    "gibbs": ("f32",),
    "dsim": ("f32", "int8"),
    "dsim_dist": ("f32", "int8", "bitplane"),
    "lattice": ("f32", "int8", "bitplane"),
}
# canonical word-format constants live next to the packing routines
from repro.core.packing import LANE_WIDTH, MAX_LANE_WORDS  # noqa: E402
from repro.obs import flip_syncs  # noqa: E402
from repro.obs.trace import span  # noqa: E402


def lanes_of(precision: str) -> int:
    """Replica lanes one engine call packs per word (1 off the bitplane
    path) — the quantum the serving scheduler clamps batch widths to."""
    return LANE_WIDTH if precision == "bitplane" else 1


def lane_words(n_lanes: int) -> int:
    """Word planes needed for ``n_lanes`` packed lanes: W = ceil(L/32)."""
    return (int(n_lanes) + LANE_WIDTH - 1) // LANE_WIDTH


def check_lanes(precision: str, replicas: int,
                max_words: int = MAX_LANE_WORDS,
                what: str = "replicas") -> int:
    """The one lane-cap guard every packed path shares.

    Validates ``replicas`` (>= 1 on any precision; <= ``max_words * 32``
    on the bitplane path, where they become bit lanes of stacked uint32
    word planes) and returns the word count W the packed state will carry
    — 1 for unpacked precisions.  ``what`` names the quantity in the error
    (the packed tempering ladder passes "chains*temperatures")."""
    r = int(replicas)
    if r < 1:
        raise ValueError(f"{what} must be >= 1, got {r}")
    if precision != "bitplane":
        return 1
    cap = int(max_words) * LANE_WIDTH
    if r > cap:
        raise ValueError(
            f"precision='bitplane' packs {what} into the bit lanes of up "
            f"to {int(max_words)} stacked uint32 word planes; {what} must "
            f"be in [1, {cap}], got {r}")
    return lane_words(r)


def check_precision(engine: str, precision: str):
    """Registry-level guard: raise a clear ValueError for an unknown
    precision or an (engine, precision) pair no backend implements."""
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}; choose from "
                         f"{PRECISIONS}")
    ok = ENGINE_PRECISIONS.get(engine, ("f32",))
    if precision not in ok:
        raise ValueError(
            f"precision={precision!r} is not supported on engine "
            f"{engine!r} (supported: {', '.join(ok)})"
            + ("; bit-plane multi-spin coding is a lattice/dsim_dist path"
               if precision == "bitplane" else ""))


@runtime_checkable
class Engine(Protocol):
    """What every sampling backend exposes to callers.

    ``replicas`` (R) is fixed at construction; states carry a leading
    replica axis and all traces are per-replica.
    """

    replicas: int
    n_sites: int

    def init_state(self, seed: int = 0) -> Any:
        """Fresh replicated sampler state (R independent RNG streams)."""

    def run_recorded(self, state, schedule, record_points: Sequence[int],
                     sync_every: SyncSpec = 1):
        """Run to each record point; returns (state, RunRecord)."""

    def energy(self, state) -> jnp.ndarray:
        """(R,) true global energies of the current configurations."""

    def global_spins(self, state) -> jnp.ndarray:
        """(R, N) spins in the original problem's node order."""

    def lower_chunk(self, iters: int = 2, S: int = 4):
        """Lower (not run) one sampling chunk — dry-run/roofline hook."""


@dataclasses.dataclass
class RunRecord:
    """Recorded trajectory: unpacks like the legacy ``(times, energies)``
    pair; ``flips`` rides along as the exact host-side total."""

    times: np.ndarray          # (P,) sweep indices of the record points
    energies: jnp.ndarray      # (P,) or (P, R) energies at those points
    flips: int = 0             # exact accepted-flip total (Python int)

    def __iter__(self):
        return iter((self.times, self.energies))

    def __len__(self):
        return 2

    def __getitem__(self, i):
        return (self.times, self.energies)[i]


def chunk_plan(points: Sequence[int],
               max_chunk: Optional[int] = None) -> List[int]:
    """Decompose gaps between record points into power-of-two chunks.

    Returns a list of chunk lengths whose cumsum passes through every point,
    using only power-of-two lengths so at most log2(max_gap) distinct jit
    signatures are compiled.  ``max_chunk`` (a power of two) additionally
    caps each chunk — used to bound per-chunk flip counts below 2**31.
    """
    if max_chunk is not None:
        if max_chunk < 1 or max_chunk & (max_chunk - 1):
            raise ValueError(f"max_chunk must be a power of two, got {max_chunk}")
    plan: List[int] = []
    prev = 0
    for p in points:
        gap = int(p) - prev
        if gap < 0:
            raise ValueError("record points must be nondecreasing")
        while gap > 0:
            c = 1 << (gap.bit_length() - 1)
            if max_chunk is not None:
                c = min(c, max_chunk)
            plan.append(c)
            gap -= c
        prev = int(p)
    return plan


def flips_chunk_cap(flips_per_sweep: int, sweeps_per_iter: int = 1) -> int:
    """Largest power-of-two iteration chunk whose worst-case flip count
    stays below 2**31 (so int32 deltas are exact)."""
    per_iter = max(int(flips_per_sweep), 1) * max(int(sweeps_per_iter), 1)
    cap = max((1 << 30) // per_iter, 1)
    return 1 << (cap.bit_length() - 1)


def quantize_record_points(record_points: Sequence[int], S: int,
                           limit: Optional[int] = None) -> List[int]:
    """Record points snapped to multiples of the exchange period S.

    ``limit`` (the schedule length): round-to-nearest can push a valid
    point past the end of the schedule (e.g. 1000 with S=7 -> 1001), so
    when given, quantized points clamp down to the last reachable
    boundary ``(limit // S) * S``.
    """
    pts = set(max(S, int(round(p / S)) * S) for p in record_points)
    if limit is not None:
        last = (int(limit) // S) * S
        if last >= S:
            pts = set(min(p, last) for p in pts)
    return sorted(pts)


def _flips_read(value) -> np.ndarray:
    return np.atleast_1d(np.asarray(value)).astype(np.int64) % (1 << 32)


# Chunks the host may have dispatched and not seen finish.  At two the
# device always has the next chunk queued while the host prepares the one
# after, and the host never queues a whole anneal of state buffers.
_IN_FLIGHT = 2


class RecordedCursor:
    """The shared recording loop in resumable form.

    Same chunk plan, record-point quantization, and exact modular flip
    accounting as :func:`run_recorded_driver` — but advanced one bounded
    chunk at a time (:meth:`advance`), so a scheduler can interleave several
    runs on one device, stream partial traces to callers mid-anneal, and
    preempt a long job between chunks.  Driving a cursor to completion is
    bitwise identical to the one-shot driver; ``run_recorded_driver`` *is*
    a cursor driven to completion.

    Args are those of :func:`run_recorded_driver`.  Mid-run, :meth:`record`
    returns an exact snapshot (times/observables recorded so far, exact
    flips so far); :attr:`flips_vec` additionally keeps the per-counter
    (e.g. per-replica) totals so a multi-tenant caller can attribute flips
    to the replica slices it packed into one batched run.

    Advancing never reads the flip counters on the host: record and bound
    points take device-side snapshots, which :meth:`record`,
    :meth:`run_to_completion`, :meth:`checkpoint` and reads of
    :attr:`flips` / :attr:`flips_vec` settle in one host read.  At most
    :data:`_IN_FLIGHT` dispatched chunks are left unconfirmed.
    """

    def __init__(self, *, state, schedule, record_points: Sequence[int],
                 chunk_fn: Callable, record_fn: Callable,
                 sync_every: SyncSpec = 1,
                 flips_of: Optional[Callable] = None,
                 flips_per_sweep: Optional[int] = None):
        if len(record_points) == 0:
            raise ValueError("record_points must be non-empty")
        S = 1 if sync_every in ("phase", None) else int(sync_every)
        if S < 1:
            raise ValueError(f"sync_every must be >= 1, got {sync_every!r}")
        betas = np.asarray(schedule.beta_array())
        if max(int(p) for p in record_points) > len(betas):
            raise ValueError("schedule shorter than last record point")
        pts = quantize_record_points(record_points, S, limit=len(betas))
        if len(betas) < pts[-1]:
            raise ValueError("schedule shorter than last record point")
        max_chunk = None
        if flips_per_sweep is not None:
            max_chunk = flips_chunk_cap(flips_per_sweep, S)
        self.state = state
        self.S = S
        self.total_sweeps = pts[-1]
        self._betas = betas
        self._chunk_fn = chunk_fn
        self._record_fn = record_fn
        self._flips_of = flips_of
        self._flips_per_sweep = flips_per_sweep
        self._plan = chunk_plan([p // S for p in pts], max_chunk=max_chunk)
        self._targets = set(pts)
        self._i = 0                  # next chunk index into the plan
        self._pos = 0                # sweeps completed
        self._out: List[Any] = []
        self._times: List[int] = []
        # optional per-chunk boundary hook (fault injection: the serving
        # layer's FaultPlan raises/hangs/corrupts here, at exactly the
        # points where the hardware would drop a boundary exchange)
        self.fault_hook: Optional[Callable] = None
        # optional per-chunk timer `(sweeps, seconds) -> None` (telemetry:
        # obs.EtaMeter attaches here).  When set, each chunk is bracketed
        # by block_until_ready so device-async work is attributed to the
        # chunk that launched it; when None (default) no sync is added
        # and chunks stay in flight.
        self.chunk_timer: Optional[Callable] = None
        # Flip counters (module docstring): `_prev` is the last settled
        # value, `_snaps` the snapshots taken since.
        self._prev = _flips_read(flips_of(state)) if flips_of is not None \
            else None
        self._pending = 0            # worst-case flips since the last snapshot
        self._snaps: List[Any] = []  # snapshots not yet folded into totals
        self._snap_pos = 0           # sweep position of the newest snapshot
        # (sweep position, flip counter) of chunks not yet seen finished
        self._inflight: collections.deque = collections.deque()
        self._flips_vec = None if self._prev is None else \
            np.zeros(self._prev.shape, np.int64)
        self._flips_total = 0        # exact host total (Python int)

    _LIMIT = 1 << 31

    @property
    def done(self) -> bool:
        return self._i >= len(self._plan)

    @property
    def sweeps_done(self) -> int:
        return self._pos

    @property
    def points_recorded(self) -> int:
        """How many record points have been hit so far (no device sync) —
        lets a caller skip :meth:`record` after a mid-gap chunk."""
        return len(self._times)

    @property
    def flips(self) -> int:
        """Exact flips up to the last record or bound point (settles the
        snapshots taken since the last read)."""
        self._read_flips()
        return self._flips_total

    @property
    def flips_vec(self) -> Optional[np.ndarray]:
        """Exact per-counter totals up to the last record or bound point
        (settles like :attr:`flips`); None without counters."""
        self._read_flips()
        return self._flips_vec

    @flips_vec.setter
    def flips_vec(self, vec):
        self._flips_vec = vec

    def _snapshot(self):
        """Take the current flip counters without a host read."""
        cnt = self._flips_of(self.state)
        if isinstance(cnt, jax.Array):
            cnt.copy_to_host_async()
        else:
            cnt = np.array(cnt)
        self._snaps.append(cnt)
        self._snap_pos = self._pos
        self._pending = 0
        flip_syncs.count("snapshot")

    def _read_flips(self):
        """Settle: fold every pending snapshot, in order, into the exact
        totals, with one host read of them all."""
        if not self._snaps:
            return
        with span("cursor.read_flips"):
            for value in jax.device_get(self._snaps):
                cur = _flips_read(value)
                delta = (cur - self._prev) % (1 << 32)
                self._flips_vec += delta
                self._flips_total += int(delta.sum())
                self._prev = cur
            self._snaps = []
            while self._inflight and self._inflight[0][0] <= self._snap_pos:
                self._inflight.popleft()     # finished: its result was read
            flip_syncs.count("settle")

    def _sync_flips(self):
        """Exact flips up to the current position."""
        if self._flips_of is not None and self._pending:
            self._snapshot()
        self._read_flips()

    def _wait_in_flight(self):
        """Leave the device at most one chunk queued behind the running
        one before the next is dispatched."""
        if len(self._inflight) < _IN_FLIGHT:
            return
        with span("cursor.wait"):
            jax.block_until_ready(self._inflight.popleft()[1])
        flip_syncs.count("wait")

    def advance(self, max_chunks: int = 1) -> int:
        """Run up to ``max_chunks`` plan chunks; returns how many ran."""
        ran = 0
        while ran < max_chunks and not self.done:
            if self.fault_hook is not None:
                self.fault_hook(self)
            c = self._plan[self._i]
            nsw = c * self.S
            worst = nsw * (self._flips_per_sweep or 0)
            if self._flips_of is not None and self._flips_per_sweep and \
                    self._pending + worst >= self._LIMIT:
                self._snapshot()
            self._wait_in_flight()
            with span("cursor.chunk"):
                # trailing dims (e.g. a per-replica axis) ride along
                # untouched; the slice stays on the host, for the chunk to
                # put where its program wants it
                bchunk = self._betas[self._pos:self._pos + nsw].reshape(
                    (c, self.S) + self._betas.shape[1:])
                if self.chunk_timer is not None:
                    jax.block_until_ready(self.state)
                    t0 = time.perf_counter()
                    self.state = self._chunk_fn(self.state, bchunk, c, self.S)
                    jax.block_until_ready(self.state)
                    self.chunk_timer(nsw, time.perf_counter() - t0)
                else:
                    self.state = self._chunk_fn(self.state, bchunk, c, self.S)
            self._i += 1
            self._pos += nsw
            self._pending += worst
            ran += 1
            if self._flips_of is not None and self.chunk_timer is None:
                cnt = self._flips_of(self.state)
                if isinstance(cnt, jax.Array):
                    self._inflight.append((self._pos, cnt))
            if self._flips_of is not None and self._flips_per_sweep is None:
                self._snapshot()     # unknown bound: a snapshot per chunk
            if self._pos in self._targets:
                with span("cursor.readout"):
                    self._out.append(self._record_fn(self.state))
                self._times.append(self._pos)
                if self._flips_of is not None:
                    self._snapshot()
        return ran

    def run_to_completion(self):
        self.advance(max_chunks=len(self._plan))
        self._sync_flips()
        return self

    def record(self) -> RunRecord:
        """Exact snapshot of the trajectory recorded so far.

        Mid-run this settles the pending flip window (one host sync — the
        caller is asking for an exact partial result); after
        :meth:`run_to_completion` it is free.  With no record points hit
        yet, ``energies`` is an empty (0,) array.
        """
        with span("cursor.record"):
            self._sync_flips()
            obs = jnp.stack(self._out) if self._out else jnp.zeros((0,))
            return RunRecord(np.asarray(self._times, np.int64), obs,
                             self._flips_total)

    def warm(self):
        """Execute each distinct chunk length once, discarding the result.

        Chunk runners jit-compile per (length, S) signature; running every
        distinct length in the plan on the *initial* state populates those
        caches without advancing the cursor (chunk_fn is pure), so a serving
        layer can absorb cold-start compiles off the request's timed path.
        The record observable is warmed too (it may be jitted, e.g. the
        partitioned engines' energy readout).
        """
        seen = set()
        for c in self._plan[self._i:]:
            if c in seen:
                continue
            seen.add(c)
            nsw = c * self.S
            bchunk = self._betas[:nsw].reshape(
                (c, self.S) + self._betas.shape[1:])
            jax.block_until_ready(self._chunk_fn(self.state, bchunk, c,
                                                 self.S))
        if not self.done:
            jax.block_until_ready(self._record_fn(self.state))
        return self

    # -- checkpoint / resume ---------------------------------------------------

    _CK_FORMAT = 1

    def checkpoint(self, snapshot_fn: Optional[Callable] = None) -> dict:
        """Picklable host-side checkpoint of the cursor mid-run.

        Captures everything :meth:`restore_checkpoint` needs to continue
        the run bitwise-identically on a *fresh* cursor built from the
        same (schedule, record points, sync_every): plan position,
        recorded times/observables so far, the exact modular flip
        accounting (``_prev``/``_pending``/totals), and the engine state
        — via ``snapshot_fn`` (normally the handle's ``snapshot``, which
        pulls device arrays to owned numpy copies) or raw.  Settles the
        pending flip window first, so the checkpoint's counters are exact
        at this boundary.
        """
        self._sync_flips()
        snap = self.state if snapshot_fn is None else snapshot_fn(self.state)
        return {
            "format": self._CK_FORMAT,
            "S": self.S,
            "total_sweeps": self.total_sweeps,
            "plan_len": len(self._plan),
            "i": self._i,
            "pos": self._pos,
            "times": list(self._times),
            "out": [np.asarray(o) for o in self._out],
            "prev": None if self._prev is None else self._prev.copy(),
            "pending": self._pending,
            "flips_vec": None if self._flips_vec is None
            else self._flips_vec.copy(),
            "flips_total": self._flips_total,
            "state": snap,
        }

    def restore_checkpoint(self, ck: dict,
                           restore_fn: Optional[Callable] = None):
        """Resume a fresh cursor from :meth:`checkpoint` output.

        The cursor must have been constructed with the same schedule,
        record points, and sync period — validated against the
        checkpoint's (S, total_sweeps, plan length) triple; a mismatch
        raises ValueError (the caller restarts from sweep 0 instead of
        silently resuming into a different trajectory).  With a matching
        plan the continuation is bitwise-identical to the uninterrupted
        run.  ``restore_fn`` (normally the handle's ``restore``) pushes
        the state snapshot back to device, re-sharded where the engine
        shards.
        """
        if ck.get("format") != self._CK_FORMAT:
            raise ValueError(f"unknown checkpoint format "
                             f"{ck.get('format')!r}")
        have = (ck["S"], ck["total_sweeps"], ck["plan_len"])
        want = (self.S, self.total_sweeps, len(self._plan))
        if have != want:
            raise ValueError(
                f"checkpoint plan mismatch: checkpoint has (S, sweeps, "
                f"chunks)={have}, cursor has {want}")
        self.state = ck["state"] if restore_fn is None \
            else restore_fn(ck["state"])
        self._i = int(ck["i"])
        self._pos = int(ck["pos"])
        self._times = [int(t) for t in ck["times"]]
        self._out = [jnp.asarray(o) for o in ck["out"]]
        self._prev = None if ck["prev"] is None \
            else np.asarray(ck["prev"]).copy()
        self._pending = int(ck["pending"])
        self._snaps = []
        self._inflight.clear()
        self.flips_vec = None if ck["flips_vec"] is None \
            else np.asarray(ck["flips_vec"]).copy()
        self._flips_total = int(ck["flips_total"])
        return self


def run_recorded_driver(*, state, schedule, record_points: Sequence[int],
                        chunk_fn: Callable,
                        record_fn: Callable,
                        sync_every: SyncSpec = 1,
                        flips_of: Optional[Callable] = None,
                        flips_per_sweep: Optional[int] = None):
    """The shared recording loop (a :class:`RecordedCursor` driven to
    completion).

    Args:
      state: engine state (any pytree).
      schedule: a ``repro.core.annealing.Schedule``.
      record_points: sweep indices at which to record (non-empty).
      chunk_fn: ``(state, betas_2d, iters, S) -> state`` runs ``iters``
        iterations of ``S`` sweeps; betas_2d is a host array of shape
        (iters, S), which jit takes as it is or the chunk puts itself.
      record_fn: ``state -> observable`` read at each record point.
      sync_every: int S (exchange every S sweeps), 'phase', or None —
        engines that don't exchange just ignore it in their chunk_fn.
      flips_of: optional ``state -> int32 array`` cumulative device flip
        counter(s); when given, the driver accumulates the exact total.
      flips_per_sweep: worst-case flips per sweep (usually N sites times
        replicas); bounds chunk sizes so int32 deltas never alias.

    Returns (state, RunRecord).
    """
    cur = RecordedCursor(
        state=state, schedule=schedule, record_points=record_points,
        chunk_fn=chunk_fn, record_fn=record_fn, sync_every=sync_every,
        flips_of=flips_of, flips_per_sweep=flips_per_sweep)
    cur.run_to_completion()
    return cur.state, cur.record()


# ---------------------------------------------------------------------------
# replica helpers
# ---------------------------------------------------------------------------

def spawn_seeds(seed: int, replicas: int) -> List[int]:
    """R independent 31-bit seeds derived from one master seed.

    Uses numpy's SeedSequence spawning, so replica streams are statistically
    independent and replica r of (seed, R) equals replica r of (seed, R')
    for r < min(R, R') — growing the replica batch never reshuffles the
    existing chains.
    """
    ss = np.random.SeedSequence(seed)
    return [int(child.generate_state(1)[0] & 0x7FFFFFFF)
            for child in ss.spawn(replicas)]


def stack_states(states: Sequence[Any]):
    """Stack per-replica state pytrees along a new leading replica axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *states)
