"""amtrace + amscope — observability for the batched merge pipeline and
the serving stack (SURVEY §5.1).

Five parts plus a CLI:

- **Spans** (`obs.spans`): nested wall-clock span trees with per-span call
  counts and fixed-bucket latency histograms (p50/p95/p99), ambient
  propagation via ``contextvars`` (thread/task safe), JSON-lines export
  and an indented tree-table renderer. ``automerge_tpu/profiling.py`` is a
  thin compatibility shim over this layer — ``PhaseProfile`` /
  ``get_profile`` / ``use_profile`` keep working unchanged.
- **Metrics** (`obs.metrics`): counters/gauges/histograms in one
  process-wide registry — farm batch occupancy, engine jit cache hits vs
  recompiles, sync message/byte/Bloom accounting. Histogram buckets carry
  **exemplars** (recent trace ids), so a p99 spike links to the request
  trace behind it. Disabled by default; recording costs one attribute
  test until a workload enables the registry.
- **Request-flow tracing** (`obs.scope`, "amscope"): per-request trace
  contexts attached at the serving front door and carried through the
  batching window into the batched farm dispatch — one dispatch span
  links the N request traces it served and carries the shared per-phase
  breakdown; per-tenant accounting rides along.
- **Flight recorder** (`obs.flight`): a bounded ring of structured events
  (retransmits, watchdog escalations, quarantine transitions, flush
  decisions, recompiles, slab growth), snapshot-dumped to JSONL on
  faults for postmortems without re-running the workload. Mesh workers
  ship their shard-tagged event tails over the result pipe into the
  controller's unified timeline, and persist a bounded black-box file
  for crash forensics that survive a SIGKILL.
- **Live telemetry** (`obs.export`): Prometheus-style text exposition
  (mounted on the asyncio adapter's telemetry port), periodic JSONL
  snapshots, and the per-request phase-share math.
- **amprof** (`obs.prof`): the compiled-program
  observatory — every tpu-layer jit program registers a named
  ``ProfiledProgram`` wrapper recording per-program compile/dispatch
  tallies, latency histograms and shape buckets, with a recompile-storm
  detector — plus the memory ``Sampler`` (slab pages, DecodeCache and
  change-column bytes as ``prof.mem.*`` gauges).
- **SLOs** (`obs.slo`): declared objectives (latency percentile under
  budget, availability, convergence ratio) evaluated as multi-window
  burn rates on an injected clock — simulated and wall clocks both
  work — exported as ``slo.*`` gauges and verdict dicts that gate the
  serve/mesh benches.
- **CLI**: ``python -m automerge_tpu.obs`` runs a canned farm merge + sync
  round-trip (or reads a dumped JSONL trace); ``--flight`` renders a
  flight-recorder dump as a causal timeline; ``--watch`` renders live
  telemetry snapshots top-style. See the README "Observability" section
  for the metric and event catalogs (cross-checked by amlint AM304).

Everything here is host-side and stdlib-only: importing ``obs`` never
initialises jax, and amlint rule AM303 keeps instrument calls out of
jit/vmap/Pallas-reachable code.
"""
# amlint: host-only — pure-host layer: must not import tpu/ or jax
from __future__ import annotations

import contextlib

from .flight import FlightRecorder, enabled_flight, get_flight
from .metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    enabled_metrics,
    get_metrics,
)
from .prof import (
    Observatory,
    ProfiledProgram,
    Sampler,
    enabled_observatory,
    get_observatory,
)
from .scope import (
    Amscope,
    DispatchSpan,
    RequestScope,
    enabled_amscope,
    get_amscope,
)
from .slo import (
    Objective,
    SLOEngine,
    availability_objective,
    latency_objective,
    ratio_objective,
    verdicts_ok,
)
from .spans import SpanNode, Trace, get_trace, use_trace

__all__ = [
    "Amscope",
    "Counter",
    "DispatchSpan",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "Objective",
    "Observatory",
    "ProfiledProgram",
    "RequestScope",
    "SLOEngine",
    "Sampler",
    "SpanNode",
    "Trace",
    "availability_objective",
    "enabled_amscope",
    "enabled_flight",
    "enabled_metrics",
    "enabled_observability",
    "enabled_observatory",
    "get_amscope",
    "get_flight",
    "get_metrics",
    "get_observatory",
    "get_trace",
    "latency_objective",
    "ratio_objective",
    "use_trace",
    "verdicts_ok",
]


@contextlib.contextmanager
def enabled_observability(flight_dir: str | None = None):
    """Enables the whole observability stack — metrics registry, amscope
    request tracing, the flight recorder and the amprof observatory —
    for the dynamic extent, restoring every previous enabled state on
    exit. The one-call opt-in the load harness and bench workloads use."""
    with enabled_metrics(), enabled_amscope(), enabled_flight(
        dump_dir=flight_dir
    ), enabled_observatory():
        yield
