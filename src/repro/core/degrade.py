"""Degraded-mode fabric for the mesh engines: integrity + failover policy.

The paper's central claim is that a partitioned sampler tolerates *stale*
boundary information — convergence persists with a quantifiably reduced
power-law exponent, governed by eta = f_comm/f_pbit.  This module turns
that physics into the machine's failure-handling contract:

* every boundary exchange carries a **wire header** ``[seq, checksum]``
  (uint32 each) alongside the payload, so a corrupted, dropped, or
  out-of-order exchange is *detected* by the receiver instead of ingested;
* a :class:`DegradePolicy` says what happens next — ``fail_fast`` raises
  :class:`StateCorruption` at the first detection, ``stale_hold`` keeps
  sweeping on last-known-good ghosts until a per-source staleness budget
  is exhausted, ``freeze_boundary`` pins the boundary permanently after
  the first detection and never escalates;
* a :class:`MeshHealthMonitor` keeps the host-side view: cumulative
  detection/held counters, per-source staleness, quarantine (``suspect``)
  marking, and the ``resync()`` bookkeeping when an engine forces an
  instantaneous full-boundary refresh.

The in-trace side: the health carry is a 6-tuple of replicated
scalars/vectors threaded through the chunk scan; :func:`fault_code` and
:func:`health_step` are the integrity step both mesh engines share, and
each engine's checked exchange (``core/dsim_dist.py`` /
``core/lattice_dsim.py``) keeps only its own wire.  Held exchanges are
``jnp.where`` selections against the carried (last-known-good) ghosts, so
a run with zero detections is bitwise identical to an unchecked run.

Wire checksum: a position-weighted modular sum over the payload viewed as
uint32 words — ``sum(w_i * (i * 2654435761 + 1)) mod 2^32``.  The odd
per-position weights make it order-sensitive (a swapped pair of words is
detected, unlike a plain sum) while staying one multiply-add per word.
"""

from __future__ import annotations

import dataclasses
from typing import ClassVar, Optional, Tuple, Union

import numpy as np

__all__ = ["StateCorruption", "DegradePolicy", "MeshHealthMonitor",
           "health_init", "fault_code", "health_step", "wire_checksum",
           "wire_words", "DEGRADE_MODES"]

DEGRADE_MODES = ("fail_fast", "stale_hold", "freeze_boundary")

# one odd multiplier per word position (Knuth's 2^32/phi); position-
# sensitive so reordered payload words fail the check
_CK_MULT = 2654435761


class StateCorruption(RuntimeError):
    """Engine state failed an integrity check.

    Raised by the serving integrity guard (non-finite recorded energies)
    and by the degraded-mode mesh when a :class:`DegradePolicy` escalates:
    ``fail_fast`` at the first detected-bad exchange, ``stale_hold`` when
    a boundary source exceeds its staleness budget.  Classified transient
    by ``serve.faults.classify_error`` — a retry from the last checkpoint
    re-runs the trajectory with fresh state.
    """


@dataclasses.dataclass(frozen=True)
class DegradePolicy:
    """What a mesh engine does when a boundary exchange fails integrity.

    mode:
      * ``"fail_fast"``      — raise :class:`StateCorruption` at the first
                               detection (the pre-degraded-mode behavior,
                               made explicit and immediate).
      * ``"stale_hold"``     — hold last-known-good ghosts for the bad
                               source(s), keep sweeping, escalate once any
                               source's consecutive-held count exceeds
                               ``max_staleness`` exchanges.
      * ``"freeze_boundary"``— after the first detection, pin ALL boundary
                               ghosts permanently (the mesh decouples into
                               independent bricks); never escalates.

    ``max_staleness`` is counted in *exchanges* (one per ``sync_every``
    sweeps), per source — partition k for ``dsim_dist``, face index for
    the lattice engine.
    """

    mode: str = "stale_hold"
    max_staleness: int = 8

    MODES: ClassVar[Tuple[str, ...]] = DEGRADE_MODES

    def __post_init__(self):
        if self.mode not in DEGRADE_MODES:
            raise ValueError(f"unknown degrade mode {self.mode!r}; "
                             f"expected one of {DEGRADE_MODES}")
        if int(self.max_staleness) < 0:
            raise ValueError("max_staleness must be >= 0")

    @classmethod
    def parse(cls, spec: Union[None, str, "DegradePolicy"]) \
            -> Optional["DegradePolicy"]:
        """None | DegradePolicy | "fail_fast" | "stale_hold[:N]" |
        "freeze_boundary" -> DegradePolicy (or None)."""
        if spec is None or isinstance(spec, cls):
            return spec
        if isinstance(spec, str):
            name, _, arg = spec.partition(":")
            if arg and name != "stale_hold":
                raise ValueError(
                    f"degrade policy {spec!r}: only stale_hold takes a "
                    "staleness budget")
            if name == "stale_hold" and arg:
                return cls(name, int(arg))
            return cls(name)
        raise TypeError(f"cannot parse degrade policy from {type(spec)}")

    def key(self) -> str:
        """Canonical string form (hashable, round-trips through parse)."""
        if self.mode == "stale_hold":
            return f"stale_hold:{int(self.max_staleness)}"
        return self.mode


def health_init(n_sources: int) -> tuple:
    """Fresh health carry: (seq, stale[n_sources], frozen, detections,
    held, max_staleness) — uint32 exchange counter, per-source consecutive-
    held counts, sticky freeze flag, and cumulative event counters.  Plain
    numpy scalars/arrays; jit converts at the boundary."""
    return (np.uint32(0), np.zeros(int(n_sources), np.int32), np.int32(0),
            np.int32(0), np.int32(0), np.int32(0))


def fault_code(codes, seq):
    """(corrupt, drop) flags of the host-scheduled fault at exchange
    ``seq`` (``codes[seq]``: 0 ok, 1 drop, 2 corrupt; none past the end)."""
    import jax.numpy as jnp
    total = jnp.uint32(codes.shape[0])
    code = jnp.where(seq < total,
                     codes[jnp.clip(seq, 0, total - 1).astype(jnp.int32)], 0)
    return code == 2, code == 1


def health_step(health, ok, freeze: bool):
    """One checked exchange's bookkeeping: ``ok`` (n_sources,) says which
    sources passed the wire check.  Returns (bad, health'): ``bad`` marks
    the sources to hold at last-known-good (every source once frozen, under
    ``freeze``), and the carry advances its counters and ``seq``."""
    import jax.numpy as jnp
    seq, stale, frozen, det, held, maxst = health
    if freeze:
        frozen = jnp.maximum(frozen, (~ok).any().astype(jnp.int32))
        bad = (~ok) | (frozen > 0)
    else:
        bad = ~ok
    det = det + (~ok).any().astype(jnp.int32)
    held = held + bad.any().astype(jnp.int32)
    stale = jnp.where(bad, stale + 1, 0)
    maxst = jnp.maximum(maxst, stale.max())
    seq = seq + jnp.uint32(1)
    return bad, (seq, stale, frozen, det, held, maxst)


def wire_words(x):
    """Reinterpret an exchange payload as uint32 words for checksumming.

    int8 planes widen via a uint8 bitcast (sign-safe), f32 pools bitcast
    directly, native word planes pass through — so sender and receiver
    checksum the exact same bit pattern regardless of which representation
    each side holds.
    """
    import jax
    import jax.numpy as jnp
    if x.dtype == jnp.uint32:
        return x
    if x.dtype == jnp.int8:
        return jax.lax.bitcast_convert_type(x, jnp.uint8).astype(jnp.uint32)
    if x.dtype == jnp.float32:
        return jax.lax.bitcast_convert_type(x, jnp.uint32)
    return x.astype(jnp.uint32)


def wire_checksum(x) -> "jnp.ndarray":
    """Position-weighted modular checksum of a payload (scalar uint32)."""
    import jax.numpy as jnp
    w = wire_words(x).reshape(-1)
    mult = (jnp.arange(w.shape[0], dtype=jnp.uint32) * jnp.uint32(_CK_MULT)
            + jnp.uint32(1))
    return (w * mult).sum(dtype=jnp.uint32)


class MeshHealthMonitor:
    """Host-side keeper of a mesh engine's exchange-health carry.

    The engine threads the carry (see :func:`health_init`) through its
    jitted chunk; after every chunk it hands the updated carry back via
    :meth:`update`, which pulls the counters to the host, feeds the
    cumulative totals, and enforces the policy (raising
    :class:`StateCorruption` when it escalates).  ``resync()`` on the
    engine calls :meth:`on_resync` to clear staleness/quarantine after an
    instantaneous full-boundary refresh.

    Counter semantics (all cumulative over the current run):

    * ``detections``        — exchanges where >= 1 source failed the wire
                              check (the integrity counter).
    * ``stale_exchanges``   — exchanges where >= 1 source was *held* at
                              last-known-good (== detections under
                              stale_hold/fail_fast; larger under
                              freeze_boundary, which holds forever).
    * ``max_staleness_seen``— worst consecutive-held count of any source.
    * ``exchanges_total``   — exchanges attempted (host-side: the chunk
                              iteration count, fed by the engine).
    """

    def __init__(self, policy: DegradePolicy, n_sources: int,
                 kind: str = "partitions"):
        self.policy = policy
        self.n_sources = int(n_sources)
        self.kind = kind
        self.resyncs = 0
        self.reset()

    def reset(self):
        """Fresh carry + counters (called at the start of every run)."""
        self.carry = health_init(self.n_sources)
        self.exchanges_total = 0
        self.detections = 0
        self.stale_exchanges = 0
        self.max_staleness_seen = 0

    @property
    def suspect(self) -> bool:
        """Quarantine mark: any source has failed integrity and no resync
        has cleared the staleness since."""
        return bool(np.asarray(self.carry[1]).max(initial=0) > 0
                    or int(self.carry[2]) > 0)

    @property
    def staleness(self) -> np.ndarray:
        """Per-source consecutive-held exchange counts (copy)."""
        return np.asarray(self.carry[1]).copy()

    @property
    def delivered_fraction(self) -> float:
        """Fraction of exchanges fully ingested — the effective-eta factor
        (eta scales with delivered boundary-refresh frequency)."""
        if not self.exchanges_total:
            return 1.0
        return max(0.0, 1.0 - self.stale_exchanges / self.exchanges_total)

    def update(self, carry, exchanges: int):
        """Absorb a post-chunk carry, then enforce the policy.

        Host-syncs the carry (one small device->host pull per chunk — the
        documented cost of enabling a degrade policy; disabled engines pay
        nothing).  Raises :class:`StateCorruption` per the policy.
        """
        self.carry = carry
        _, _, _, det, held, maxst = (np.asarray(x) for x in carry)
        self.exchanges_total += int(exchanges)
        self.detections = int(det)
        self.stale_exchanges = int(held)
        self.max_staleness_seen = max(self.max_staleness_seen, int(maxst))
        p = self.policy
        if p.mode == "fail_fast" and self.detections:
            raise StateCorruption(
                f"boundary integrity failure: {self.detections} bad "
                f"exchange(s) detected on the {self.kind} wire "
                "(policy fail_fast)")
        if p.mode == "stale_hold" \
                and self.max_staleness_seen > p.max_staleness:
            raise StateCorruption(
                f"boundary staleness {self.max_staleness_seen} exceeded "
                f"budget {p.max_staleness} exchanges (policy stale_hold; "
                "resync() or retry required)")

    def on_resync(self):
        """Clear staleness + freeze after a full-boundary refresh; the
        cumulative detection counters are history and stay."""
        seq, stale, _, det, held, maxst = self.carry
        self.carry = (seq, np.zeros(self.n_sources, np.int32), np.int32(0),
                      det, held, maxst)
        self.resyncs += 1

    def report(self) -> dict:
        """Provenance dict (JSON-safe) for job results and dashboards."""
        return {
            "policy": self.policy.key(),
            "detections": self.detections,
            "stale_exchanges": self.stale_exchanges,
            "exchanges_total": self.exchanges_total,
            "max_staleness_seen": self.max_staleness_seen,
            "delivered_fraction": self.delivered_fraction,
            "resyncs": self.resyncs,
            "suspect": self.suspect,
            "sources": self.kind,
            "staleness": [int(v) for v in np.asarray(self.carry[1])],
        }
