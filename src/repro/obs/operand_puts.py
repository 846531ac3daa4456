"""Process-wide count of the lattice engine's operand placements.

:class:`~repro.core.lattice_dsim.LatticeDSIM` hands its mesh programs
every operand already in the sharding the program declares, so a call
dispatches without resharding anything.  It puts each operand there
itself, from the host, and keeps what it put.  One counter family of a
process-wide :class:`MetricsRegistry`, ``lattice_operand_puts_total
{operand}``, counts those puts:

* ``field`` — the chunk's field operands (masks and couplings in the
  engine's representation), once per engine;
* ``energy`` — the energy readout's active sites and couplings, once per
  engine;
* ``lut`` — a threshold LUT, replicated, once per distinct beta table;
* ``sched`` — one chunk's schedule slice, replicated, once per chunk.

``shard_state`` drops what was put, so the next run puts it once again.
A ``field`` or ``energy`` count that grows with the calls would mean the
operands are put again on every call.
"""

from __future__ import annotations

from .metrics import MetricsRegistry

__all__ = ["OPERAND_PUTS", "OPERANDS", "count", "operand_puts"]

OPERAND_PUTS = "lattice_operand_puts_total"
OPERANDS = ("field", "energy", "lut", "sched")

_registry = MetricsRegistry()
_family = _registry.counter(
    OPERAND_PUTS, "lattice mesh-program operands put in their declared "
                  "sharding, by operand")
_children = {k: _family.labels(operand=k) for k in OPERANDS}


def count(operand: str) -> None:
    """One put of ``operand`` (one of :data:`OPERANDS`)."""
    _children[operand].inc()


def operand_puts() -> MetricsRegistry:
    """The registry that holds ``lattice_operand_puts_total``."""
    return _registry
