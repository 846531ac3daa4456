"""Process-wide counts of how the recorded cursor reaches its flip counters.

:class:`~repro.engines.base.RecordedCursor` takes the device's flip
counters as device-side snapshots and reads them on the host only when a
caller asks for flips.  One counter family of a process-wide
:class:`MetricsRegistry`, ``cursor_flip_syncs_total{kind}``, counts over
every cursor of the process:

* ``snapshot`` — counters taken without a host read (a record point, a
  bound point, or every chunk where the worst case is unknown);
* ``settle`` — blocking host reads, each of every snapshot pending;
* ``wait`` — blocks on the oldest chunk in flight before the next one is
  dispatched.

``settle`` over ``snapshot`` is the share of record points that still
cost a host round trip.
"""

from __future__ import annotations

from .metrics import MetricsRegistry

__all__ = ["FLIP_SYNCS", "KINDS", "count", "flip_syncs"]

FLIP_SYNCS = "cursor_flip_syncs_total"
KINDS = ("snapshot", "settle", "wait")

_registry = MetricsRegistry()
_family = _registry.counter(
    FLIP_SYNCS, "recorded-cursor flip-counter snapshots, settles and "
                "in-flight waits")
_children = {k: _family.labels(kind=k) for k in KINDS}


def count(kind: str) -> None:
    """One event of ``kind`` (one of :data:`KINDS`)."""
    _children[kind].inc()


def flip_syncs() -> MetricsRegistry:
    """The registry that holds ``cursor_flip_syncs_total``."""
    return _registry
