"""Runtime telemetry fabric: metrics, tracing, and measured-η timing.

Stdlib, the repo's own commcost model and jax's profiler annotations
(the rest of jax is imported lazily, at explicit sync boundaries).
Seven layers:

* :mod:`.metrics` — thread-safe :class:`MetricsRegistry` of counters,
  gauges, and fixed-bucket histograms with labeled children, JSON
  snapshots, and Prometheus text exposition;
* :mod:`.trace` — bounded-ring span :class:`Tracer` with an explicit
  ``block_until_ready`` boundary for device-async attribution, every span
  on the profiler's clock; :func:`span` for the program's hot path;
* :mod:`.programs` — per-program counts of JAX traces and compiles
  (``jax_traces_total{program}``, ``jax_compiles_total{program}``);
* :mod:`.flip_syncs` — the recorded cursor's flip-counter snapshots,
  settles and in-flight waits (``cursor_flip_syncs_total{kind}``);
* :mod:`.exchanges` — the lattice engine's halo exchanges dispatched,
  across devices or within one (``lattice_exchanges_total{link}``);
* :mod:`.operand_puts` — the lattice engine's operands put in the
  shardings its programs declare (``lattice_operand_puts_total
  {operand}``);
* :mod:`.timing` — :class:`EtaMeter`, which turns per-chunk wall time
  plus exchange-only collective time into measured η = f_comm/f_pbit
  and its margin against ``commcost.eta_threshold``.
"""

from .metrics import (DEFAULT_TIME_BUCKETS, Counter, Gauge, Histogram,
                      MetricsRegistry)
from .timing import EtaMeter, dist_eta_meter, exchanges_per_sweep
from .trace import Span, Tracer, install, span

__all__ = [
    "MetricsRegistry", "Counter", "Gauge", "Histogram",
    "DEFAULT_TIME_BUCKETS",
    "Tracer", "Span", "span", "install",
    "EtaMeter", "dist_eta_meter", "exchanges_per_sweep",
]
