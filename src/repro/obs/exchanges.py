"""Process-wide count of the lattice engine's halo exchanges dispatched.

:class:`~repro.core.lattice_dsim.LatticeDSIM` refreshes its six halo faces
once per ``sync_every`` sweeps inside a sampling chunk and once per
``_refresh_halos`` (a fresh state, a resync).  One counter family of a
process-wide :class:`MetricsRegistry`, ``lattice_exchanges_total{link}``,
counts them on the host as they are dispatched, with nothing waited on:

* ``chip`` — some mesh axis with more than one device crosses a brick
  face, so the exchange is a collective between devices;
* ``local`` — every brick face wraps onto its own device (one brick).

Exchanges per sweep is 1/``sync_every`` plus one refresh per started run
over its sweeps; the exchange's device time carries the profiler name
``lattice.exchange``.
"""

from __future__ import annotations

from .metrics import MetricsRegistry

__all__ = ["EXCHANGES", "LINKS", "count", "exchanges"]

EXCHANGES = "lattice_exchanges_total"
LINKS = ("chip", "local")

_registry = MetricsRegistry()
_family = _registry.counter(
    EXCHANGES, "lattice halo exchanges dispatched, by whether they cross "
               "devices")
_children = {k: _family.labels(link=k) for k in LINKS}


def count(link: str, n: int = 1) -> None:
    """``n`` exchanges over ``link`` (one of :data:`LINKS`)."""
    _children[link].inc(n)


def exchanges() -> MetricsRegistry:
    """The registry that holds ``lattice_exchanges_total``."""
    return _registry
