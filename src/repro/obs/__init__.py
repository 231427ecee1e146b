"""Serve-plane observability: metrics registry, request tracing, exporters.

``Observability`` is the per-plane bundle a ``ServeFrontend`` owns — ONE
registry + tracer + event log + cost ledger + flight recorder shared by
the scheduler, the replica pool and every engine it spins.  ``EngineObs``
is the slice handed to one engine (same objects, plus the service labels
and that replica's chip-second meter), so engine hot-path hooks never
look their service name up.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

from repro.obs.cost import (CostLedger, ReplicaMeter,  # noqa: F401
                            dtype_nbytes, param_bytes)
from repro.obs.export import (EventLog, prometheus_text,  # noqa: F401
                              write_metrics_dump)
from repro.obs.flight import FlightConfig, FlightRecorder  # noqa: F401
from repro.obs.metrics import (DEFAULT_BUCKETS, Counter, Gauge,  # noqa: F401
                               Histogram, MetricsRegistry, log_buckets,
                               snapshot_quantile)
from repro.obs.trace import Span, Tracer, phase  # noqa: F401


@dataclass
class Observability:
    """One serve plane's shared observability surfaces."""
    registry: MetricsRegistry = field(default_factory=MetricsRegistry)
    tracer: Tracer = None
    events: EventLog = field(default_factory=EventLog)
    ledger: CostLedger = None
    flight: FlightRecorder = None

    def __post_init__(self) -> None:
        if self.tracer is None:
            self.tracer = Tracer(self.registry)
        if self.ledger is None:
            self.ledger = CostLedger(registry=self.registry)
        if self.flight is None:
            self.flight = FlightRecorder(events=self.events)

    def engine_obs(self, model: str, backend: str) -> "EngineObs":
        return EngineObs(registry=self.registry, tracer=self.tracer,
                         model=model, backend=backend,
                         cost=self.ledger, flight=self.flight)


@dataclass
class EngineObs:
    """One engine's view: the shared registry/tracer plus its labels,
    the pool ledger, and (once spun up) this replica's meter."""
    registry: MetricsRegistry
    tracer: Tracer
    model: str = ""
    backend: str = ""
    cost: Optional[CostLedger] = None
    flight: Optional[FlightRecorder] = None
    meter: Optional[ReplicaMeter] = None
