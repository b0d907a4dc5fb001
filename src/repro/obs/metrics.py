"""A small in-process metrics registry (Prometheus text exposition).

The serving engine (launch/serve_solver.py) is a long-lived loop: totals in
its final ledger say *what happened*, but operating it needs the standard
service signals — queue depth, batch width, warm/cold split, Joules and
latency per request. This module provides the three canonical instrument
types with no dependencies:

* :class:`Counter` — monotone totals (``requests_total``, ``evictions``);
* :class:`Gauge` — point-in-time levels (``queue_depth``);
* :class:`Histogram` — distributions with explicit buckets
  (``batch_width``, ``request_energy_j``, ``request_latency_s``), tracking
  cumulative bucket counts plus ``_sum``/``_count`` like the Prometheus
  client does.

:meth:`MetricsRegistry.to_prometheus` renders the text exposition format
(``--metrics-out`` on the serving CLI writes it; a scraper can lift the
file as-is); :meth:`MetricsRegistry.snapshot` returns the same state as a
JSON-ready dict, embedded in the engine ledger under ``metrics``.

:data:`REGISTRY` is the process's own registry, for what the solver does
once per process rather than per request: the set-up counters that
``SolverSession.matrix`` (host seconds of ``partition_csr`` and
``shard_matrix``) and ``SolverHandle.warm`` (compile work, from JAX's own
monitoring events through :func:`compile_totals`) add to.
"""

from __future__ import annotations

import math
import threading


class Counter:
    """Monotonically increasing total."""

    def __init__(self, name: str, help_: str = ""):
        self.name = name
        self.help = help_
        self.value = 0.0

    def inc(self, amount: float = 1.0):
        if amount < 0:
            raise ValueError(f"counter {self.name} cannot decrease")
        self.value += amount


class Gauge:
    """Point-in-time level; set/inc/dec."""

    def __init__(self, name: str, help_: str = ""):
        self.name = name
        self.help = help_
        self.value = 0.0

    def set(self, value: float):
        self.value = float(value)

    def inc(self, amount: float = 1.0):
        self.value += amount

    def dec(self, amount: float = 1.0):
        self.value -= amount


# default buckets cover microjoule-to-kilojoule energies and
# microsecond-to-minute latencies on a log scale
DEFAULT_BUCKETS = tuple(10.0 ** e for e in range(-6, 7))


class Histogram:
    """Cumulative-bucket histogram (Prometheus semantics: ``le`` bounds,
    implicit ``+Inf`` bucket, running ``_sum`` and ``_count``)."""

    def __init__(self, name: str, help_: str = "", buckets=DEFAULT_BUCKETS):
        self.name = name
        self.help = help_
        self.buckets = tuple(sorted(float(b) for b in buckets))
        self.counts = [0] * (len(self.buckets) + 1)  # + the +Inf bucket
        self.sum = 0.0
        self.count = 0

    def observe(self, value: float):
        v = float(value)
        self.sum += v
        self.count += 1
        for k, bound in enumerate(self.buckets):
            if v <= bound:
                self.counts[k] += 1
                return
        self.counts[-1] += 1

    def cumulative(self) -> list[int]:
        out, running = [], 0
        for c in self.counts:
            running += c
            out.append(running)
        return out


class MetricsRegistry:
    """Named instruments; idempotent getters (same name -> same object)."""

    def __init__(self):
        self._metrics: dict[str, object] = {}

    def _get(self, cls, name: str, help_: str, **kw):
        m = self._metrics.get(name)
        if m is None:
            m = cls(name, help_, **kw)
            self._metrics[name] = m
        elif not isinstance(m, cls):
            raise TypeError(
                f"metric {name!r} already registered as "
                f"{type(m).__name__}, not {cls.__name__}"
            )
        return m

    def counter(self, name: str, help_: str = "") -> Counter:
        return self._get(Counter, name, help_)

    def gauge(self, name: str, help_: str = "") -> Gauge:
        return self._get(Gauge, name, help_)

    def histogram(self, name: str, help_: str = "",
                  buckets=DEFAULT_BUCKETS) -> Histogram:
        return self._get(Histogram, name, help_, buckets=buckets)

    def snapshot(self) -> dict:
        """JSON-ready state of every instrument (ledger ``metrics`` block)."""
        out: dict[str, dict] = {}
        for name, m in sorted(self._metrics.items()):
            if isinstance(m, Histogram):
                out[name] = dict(
                    type="histogram",
                    buckets=list(m.buckets),
                    counts=list(m.counts),
                    sum=m.sum,
                    count=m.count,
                )
            else:
                kind = "counter" if isinstance(m, Counter) else "gauge"
                out[name] = dict(type=kind, value=m.value)
        return out

    def to_prometheus(self) -> str:
        """Text exposition format (one block per metric, sorted by name)."""
        lines: list[str] = []
        for name, m in sorted(self._metrics.items()):
            kind = (
                "counter" if isinstance(m, Counter)
                else "gauge" if isinstance(m, Gauge)
                else "histogram"
            )
            if m.help:
                lines.append(f"# HELP {name} {m.help}")
            lines.append(f"# TYPE {name} {kind}")
            if isinstance(m, Histogram):
                cum = m.cumulative()
                for bound, c in zip(m.buckets, cum[:-1]):
                    lines.append(
                        f'{name}_bucket{{le="{_fmt(bound)}"}} {c}'
                    )
                lines.append(f'{name}_bucket{{le="+Inf"}} {cum[-1]}')
                lines.append(f"{name}_sum {_fmt(m.sum)}")
                lines.append(f"{name}_count {m.count}")
            else:
                lines.append(f"{name} {_fmt(m.value)}")
        return "\n".join(lines) + "\n"


def _fmt(v: float) -> str:
    """Prometheus-friendly number rendering (integers without the .0)."""
    if math.isfinite(v) and float(v).is_integer():
        return str(int(v))
    return repr(float(v))


#: The process-wide registry of the solver's set-up counters.
REGISTRY = MetricsRegistry()

# JAX's monitoring events of a compile -> the running total they add to
_COMPILE_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_lower_seconds",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "trace_lower_seconds",
    # the XLA compile, or the persistent-cache load that replaces it
    "/jax/core/compile/backend_compile_duration": "backend_compile_seconds",
}
_COMPILE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits_total",
    "/jax/compilation_cache/cache_misses": "cache_misses_total",
}
_compile_lock = threading.Lock()
_compile_totals: dict[str, float] | None = None


def _on_duration(event: str, duration: float, **_):
    key = _COMPILE_DURATIONS.get(event)
    if key is not None:
        with _compile_lock:
            _compile_totals[key] += duration


def _on_event(event: str, **_):
    key = _COMPILE_EVENTS.get(event)
    if key is not None:
        with _compile_lock:
            _compile_totals[key] += 1


def compile_totals() -> dict[str, float]:
    """Running totals of this process's compile work since the first call:
    seconds tracing and lowering, seconds in the backend compile (or the
    persistent-cache load), and persistent-cache hits and misses. The
    first call registers the one listener on JAX's monitoring events; a
    caller takes the difference of two calls around its own work."""
    global _compile_totals
    with _compile_lock:
        if _compile_totals is None:
            from jax import monitoring

            _compile_totals = dict.fromkeys(
                [*_COMPILE_DURATIONS.values(), *_COMPILE_EVENTS.values()], 0.0)
            monitoring.register_event_duration_secs_listener(_on_duration)
            monitoring.register_event_listener(_on_event)
        return dict(_compile_totals)
