"""Per-program counts of JAX traces and compiles.

JAX reports every trace of a jitted function and every backend compile
through ``jax.monitoring``, with the function's name as ``fun_name``.
:func:`watch` registers one listener per process (calling it again does
nothing more) that counts them into two counter families of a
process-wide :class:`MetricsRegistry`:

* ``jax_traces_total{program}`` — ``/jax/core/compile/jaxpr_trace_duration``;
* ``jax_compiles_total{program}`` — ``/jax/core/compile/backend_compile_duration``,
  which, as JAX's event does, includes loads from the persistent cache.

``program`` is the jitted function's name without JAX's ``jit(...)``
wrapping, so both families name a program alike (``lattice_chunk``,
``lattice_halo_refresh``, ...): a step that recompiled shows as a count
that moved.
"""

from __future__ import annotations

import threading

from .metrics import MetricsRegistry

__all__ = ["watch", "program_name", "TRACES", "COMPILES"]

TRACES = "jax_traces_total"
COMPILES = "jax_compiles_total"
_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": TRACES,
           "/jax/core/compile/backend_compile_duration": COMPILES}

_registry = MetricsRegistry()
_lock = threading.Lock()
_watching = False


def program_name(fun_name: str) -> str:
    """JAX's name of a program without its jit wrapping:
    "jit(lattice_chunk)" and "jit_lattice_chunk" -> "lattice_chunk"."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[len("jit("):-1]
    if fun_name.startswith("jit_"):
        return fun_name[len("jit_"):]
    return fun_name


def _on_duration(event: str, duration: float, **kw) -> None:
    family = _EVENTS.get(event)
    if family is None:
        return
    program = program_name(str(kw.get("fun_name", "?")))
    _registry.counter(family).labels(program=program).inc()


def watch() -> MetricsRegistry:
    """Start counting (once per process); returns the registry that
    holds the counts."""
    global _watching
    with _lock:
        if not _watching:
            import jax
            _registry.counter(TRACES, "JAX traces of a jitted program")
            _registry.counter(COMPILES, "JAX backend compiles of a program, "
                                        "persistent-cache loads included")
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            _watching = True
    return _registry
