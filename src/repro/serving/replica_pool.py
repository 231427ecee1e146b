"""Replica pools: real engine lifecycle for the concurrent serve plane.

One ``ReplicaPool`` owns every live ``InferenceEngine`` replica in the
process, keyed by (model, backend) service. Spin-up is genuinely
expensive (param init/load + XLA compile) and measured; two warm layers
cut it down:

  * param cache — model weights stay resident after scale-to-zero (the
    paper's "warm pool"), so a re-spin skips ``init_model``; they are
    kept as ``serving_params`` stores them (layer matmul weights in the
    compute dtype);
  * code cache  — the jitted prefill/decode executables for a service
    are shared across its replicas and survive scale-to-zero, so only
    the FIRST replica of a service ever pays XLA compile (replica fork,
    analogous to reusing a baked engine image).

``scale()`` has exactly the ``scale_cb`` signature ``Orchestrator``
(Algorithm 1) calls with, so the same Spin control loop that drives the
discrete-event simulator drives these real engines. Every lifecycle
action is recorded as a ``ScaleEvent`` — the measured cold/warm start
log that calibrates the simulator's constants.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import init_model, serving_params
from repro.obs.trace import phase
from repro.models.transformer import supports_paged
from repro.serving.backend import BACKENDS
from repro.serving.engine import (DEFAULT_BLOCK_SIZE, InferenceEngine,
                                  PagedInferenceEngine, Request, SpecConfig,
                                  SpecDraft, compile_fns, compile_paged_fns)
from repro.serving.faults import FaultPlan, InjectedFault
from repro.serving.sampling import SamplingParams

_Key = Tuple[str, str]


def _compute_dtype_share(params) -> float:
    """Share of the parameter elements stored below float32: those that
    ``serving_params`` cast to the compute dtype."""
    leaves = jax.tree_util.tree_leaves(params)
    low = sum(x.size for x in leaves if x.dtype != jnp.float32)
    return low / max(1, sum(x.size for x in leaves))


@dataclass
class ScaleEvent:
    t: float                 # wall time (perf_counter) the action started
    model: str
    backend: str
    before: int              # replicas before
    after: int               # replicas after
    kind: str                # spin-cold | spin-warm | down | zero |
    #                          quarantine | drain | drained | drain-timeout
    duration_s: float        # blocking cost of the action

    def __str__(self) -> str:
        return (f"[{self.kind:>9s}] {self.model}/{self.backend} "
                f"{self.before}->{self.after} ({self.duration_s:.3f}s)")


@dataclass
class ReplicaHealth:
    """Per-replica health record (attached to each engine at spin-up).

    ``healthy`` -> ``degraded`` on a step failure (the circuit breaker
    arming), back to ``healthy`` on the next clean step, ``quarantined``
    when consecutive failures cross the breaker threshold OR the engine
    poisoned itself mid-step (host/device state no longer trusted).
    Quarantine is terminal for the replica: it is evacuated, its meter
    settled, and a substitute spun by the repair path."""
    state: str = "healthy"            # healthy | degraded | quarantined
    consecutive_failures: int = 0
    failures: int = 0                 # lifetime step failures
    last_error: str = ""
    since: float = 0.0                # when `state` was entered


class ReplicaPool:
    """All live engine replicas, plus the warm param/code caches."""

    def __init__(self, models: Dict[str, object], registry,
                 max_seq: int = 256, seed: int = 0, paged="auto",
                 block_size: int = DEFAULT_BLOCK_SIZE,
                 chunk_tokens: Optional[int] = None,
                 step_token_budget: Optional[int] = None,
                 decode_burst: int = 1, obs=None,
                 spec: Optional[SpecConfig] = None,
                 faults: Optional[FaultPlan] = None,
                 quarantine_after: int = 2,
                 drain_deadline_s: float = 30.0):
        self.models = models
        self.obs = obs                # Observability bundle (optional)
        self.reg = registry
        self.max_seq = max_seq
        self.seed = seed
        # paged KV-cache plane: "auto" pages every model family that
        # supports it (GQA transformer trunk), False forces dense engines
        self.paged = paged
        self.block_size = block_size
        # continuous-batching knobs threaded into every spun engine:
        # prefill chunk bound + per-step token budget (None: whole-prompt
        # prefill / unbounded step, the pre-chunking behavior), plus the
        # opt-in decode-burst depth (K fused decode iterations per step
        # when no prefill backlog is pending; 1 = stepwise)
        self.chunk_tokens = chunk_tokens
        self.step_token_budget = step_token_budget
        self.decode_burst = decode_burst
        # speculative decoding: one SpecConfig applies pool-wide; each
        # spun engine gets a resolved SpecDraft (draft params share the
        # warm param cache) and decides co-residency itself — a target
        # the draft can't pair with (vocab mismatch, KV pressure, or the
        # draft arch IS the target) falls back to plain fused stepwise
        self.spec = spec
        self._replicas: Dict[_Key, List[InferenceEngine]] = {
            (m, b): [] for m in models for b in registry.backends}
        self._params: Dict[str, object] = {}       # warm weights per model
        self._code: Dict[_Key, object] = {}        # compiled fns per service
        self.events: List[ScaleEvent] = []
        # (label, seconds) — same contract as Gateway.cold_starts
        self.cold_starts: List[Tuple[str, float]] = []
        # -- fault tolerance ------------------------------------------------
        # seeded chaos plan threaded into every spun engine (None: no
        # injection, zero overhead), circuit-breaker threshold, and the
        # graceful-drain deadline for scale-downs with in-flight work
        self.faults = faults
        self.quarantine_after = max(1, quarantine_after)
        self.drain_deadline_s = drain_deadline_s
        # incarnation counter per service: the Nth engine (or spin
        # attempt) ever started for (model, backend) — the identity
        # FaultSpec.replica targets, stable across quarantine/replace
        self._incarnations: Dict[_Key, int] = {}
        # draining replicas: out of placement, still stepping until
        # their in-flight work finishes (or the deadline kills them)
        self._draining: Dict[_Key, List[Tuple[InferenceEngine, float]]] = {}
        # quarantined replicas awaiting a substitute (count per service)
        self._pending_replace: Dict[_Key, int] = {}
        self.quarantines = 0              # lifetime count (all services)
        self._model_quarantines: Dict[str, int] = {}

    def _use_paged(self, model: str, backend: str) -> bool:
        """paged="auto": follow the backend profile (vllm/tgi page, trt
        keeps the dense static cache) for models whose family supports
        it; True forces paging everywhere; False forces dense."""
        if self.paged is False:
            return False
        ok = supports_paged(self.models[model]) and \
            self.max_seq % self.block_size == 0
        if self.paged == "auto":
            return ok and BACKENDS[backend].paged
        if not ok:
            raise ValueError(f"{model}: paged engines unsupported")
        return True

    # -- inspection ----------------------------------------------------------
    def replicas(self, model: str, backend: str) -> List[InferenceEngine]:
        """Replicas open for PLACEMENT (serving; draining excluded)."""
        return self._replicas[(model, backend)]

    def engines(self) -> Iterator[Tuple[_Key, InferenceEngine]]:
        """Every engine that must still be STEPPED: serving replicas
        plus draining ones (their in-flight work has to finish)."""
        for key, reps in self._replicas.items():
            for eng in reps:
                yield key, eng
        for key, dr in self._draining.items():
            for eng, _deadline in dr:
                yield key, eng

    def service_engines(self, model: str,
                        backend: str) -> List[InferenceEngine]:
        """Serving + draining engines of one service (the cancel/lookup
        surface — a request may live on a draining replica)."""
        key = (model, backend)
        return (list(self._replicas[key])
                + [e for e, _ in self._draining.get(key, ())])

    def free_slots(self, model: str, backend: str) -> int:
        return sum(e.free_slots() for e in self._replicas[(model, backend)])

    def total_replicas(self) -> int:
        return (sum(len(r) for r in self._replicas.values())
                + sum(len(d) for d in self._draining.values()))

    def has_params(self, model: str) -> bool:
        return model in self._params

    # -- paged KV-cache plane inspection ---------------------------------
    def paged_replicas(self, model: str, backend: str
                       ) -> List[PagedInferenceEngine]:
        return [e for e in self._replicas[(model, backend)] if e.paged]

    def kv_free_frac(self, model: str, backend: str) -> float:
        """Best allocatable block headroom across the service's paged
        replicas (1.0 for dense services / no live replicas — nothing to
        shed on)."""
        reps = self.paged_replicas(model, backend)
        if not reps:
            return 1.0
        return max(e.kv_free_frac() for e in reps)

    def kv_bound(self, model: str, backend: str) -> bool:
        """True when KV blocks — not decode slots — are the binding
        admission resource: compute sits idle while the pool can't back
        another sequence. A fully-leased pool with fully-busy slots is
        ordinary queueing, not block starvation."""
        reps = self.paged_replicas(model, backend)
        if not reps:
            return False
        slot_cap = sum(e.idle_slots() for e in reps)
        block_cap = sum(e.block_capacity() for e in reps)
        return block_cap < slot_cap

    def kv_stats(self, model: str) -> Optional[Dict[str, float]]:
        """Pool occupancy / prefix-cache telemetry aggregated over every
        live paged replica of ``model`` (all backend columns); None when
        the model has no live paged replicas."""
        reps = [e for b in self.reg.backends
                for e in self.paged_replicas(model, b)]
        if not reps:
            return None
        hit = sum(e.hit_tokens for e in reps)
        seen = sum(e.prompt_tokens for e in reps)

        def pressure(e) -> float:
            # bytes-grounded: fraction of the replica's KV-pool BYTES
            # that cannot back a new sequence (evictable prefix-cache
            # bytes are reclaimable, so they count as headroom)
            cap = e.pool.capacity_bytes
            if cap <= 0:
                return 1.0 - e.kv_free_frac()     # geometry not published
            free = e.pool.num_free
            if e.prefix:
                free += e.prefix.evictable_blocks()
            return 1.0 - (free * e.pool.bytes_per_block) / cap

        return {
            # pressure: headroom of the LEAST-squeezed replica — high
            # only when every replica is out of allocatable KV bytes
            "kv_pressure": min(pressure(e) for e in reps),
            "kv_occupancy": max(e.kv_used_frac() for e in reps),
            "kv_hit_rate": hit / seen if seen else 0.0,
            "kv_free_blocks": float(sum(e.pool.num_free for e in reps)),
        }

    def prefix_peek(self, model: str, backend: str, req: Request) -> int:
        """Best cached-prefix reuse (tokens) any replica offers ``req``."""
        reps = self.paged_replicas(model, backend)
        return max((e.prefix_peek(req) for e in reps), default=0)

    def backlog_tokens(self, model: str) -> int:
        """Prefill backlog in TOKENS across every live replica of
        ``model`` (engine-internal queues + unfilled prefill cursors) —
        the load measure that sees a half-prefilled 8k prompt where a
        free-slot count sees an almost-idle engine."""
        return sum(e.pending_tokens() for b in self.reg.backends
                   for e in self._replicas[(model, b)])

    # -- lifecycle (Orchestrator scale_cb target) -----------------------------
    def scale(self, model: str, backend: str, replicas: int,
              now: Optional[float] = None) -> int:
        """Bring the service to ``replicas`` live engines (blocking; real
        spin-up cost is paid inline and measured). Returns the achieved
        replica count. Scale-down retires idle replicas immediately and
        DRAINS busy ones: out of placement at once, stepped until their
        in-flight work finishes (deadline-bounded), then retired —
        nothing in flight is dropped. An injected spin failure stops
        the scale-up short (achieved < target; the next tick retries)."""
        now = time.perf_counter() if now is None else now
        entry = self.reg.entry(model, backend)
        entry.accrue(now)
        replicas = max(0, replicas)
        while len(self._replicas[(model, backend)]) < replicas:
            try:
                self._spin_up(model, backend, now)
            except InjectedFault:
                break                     # chaos: spin-up failed, no crash
        if len(self._replicas[(model, backend)]) > replicas:
            self._spin_down(model, backend, replicas, now)
        return len(self._replicas[(model, backend)])

    def evict(self, model: str) -> None:
        """Drop the warm param + code caches — next spin is a true cold."""
        self._params.pop(model, None)
        for key in [k for k in self._code if k[0] == model]:
            del self._code[key]
        for (m, _), e in self.reg.matrix.items():
            if m == model:
                e.warm = 0

    def _warm_params(self, name: str, cfg, now: float):
        """``name``'s entry of the warm param cache, built on first use:
        initialised, then stored for serving under ``pool.store_params``,
        which also sets ``params_compute_dtype_share``."""
        if name not in self._params:
            params = init_model(cfg, jax.random.PRNGKey(self.seed))
            with phase("pool.store_params"):
                params = self._params[name] = serving_params(cfg, params)
            if self.obs is not None:
                self.obs.registry.gauge(
                    "params_compute_dtype_share", name).set(
                        _compute_dtype_share(params), stamp=now)
        return self._params[name]

    def _spec_draft(self, model: str, now: float) -> Optional[SpecDraft]:
        """Resolve the pool's SpecConfig into a SpecDraft for ``model``
        (None when spec is off or the draft arch IS the target — a model
        never drafts for itself). Draft weights ride the same warm param
        cache as serving models, so scale-to-zero keeps them resident."""
        if self.spec is None or self.spec.draft_arch == model:
            return None
        arch = self.spec.draft_arch
        dcfg = self.models.get(arch)
        if dcfg is None:
            import dataclasses

            from repro.configs.registry import ARCHS
            if arch not in ARCHS:
                raise ValueError(f"unknown spec draft arch {arch!r}")
            dcfg = dataclasses.replace(ARCHS[arch].reduced(),
                                       dtype=self.models[model].dtype)
        return SpecDraft(cfg=dcfg, params=self._warm_params(arch, dcfg, now),
                         k=self.spec.k)

    # -- internals -------------------------------------------------------
    def _spin_up(self, model: str, backend: str, now: float) -> None:
        key = (model, backend)
        reps = self._replicas[key]
        # incarnation: every spin ATTEMPT gets the next identity, so a
        # fault plan can target "the substitute of replica 0" stably
        incarnation = self._incarnations.get(key, 0)
        self._incarnations[key] = incarnation + 1
        if self.faults is not None and self.faults.spin_fails(
                model, backend, incarnation):
            if self.obs is not None:
                self.obs.registry.counter(
                    "fault_injected_total",
                    f"{model}|kind=spin_fail").inc()
                self.obs.events.append("fault", t=now, model=model,
                                       backend=backend, kind="spin_fail",
                                       incarnation=incarnation)
            raise InjectedFault(
                f"injected spin_fail for {model}/{backend}#{incarnation}")
        with phase("pool.spin"):
            # servelint: disable=SL001 -- real wall interval: spin-up duration
            t0 = time.perf_counter()
            cfg = self.models[model]
            warm = model in self._params and key in self._code
            use_paged = self._use_paged(model, backend)
            params = self._warm_params(model, cfg, now)
            if key not in self._code:
                self._code[key] = (
                    compile_paged_fns(cfg, BACKENDS[backend], self.max_seq,
                                      self.block_size) if use_paged
                    else compile_fns(cfg, BACKENDS[backend], self.max_seq))
            # ONE seed pool-wide: per-request PRNG streams are keyed by uid x
            # draw index, so equal seeds make replicas interchangeable — the
            # invariant deterministic retry-on-another-replica rests on
            kw = dict(max_seq=self.max_seq,
                      seed=self.seed,
                      fns=self._code[key],
                      chunk_tokens=self.chunk_tokens,
                      step_token_budget=self.step_token_budget,
                      decode_burst=self.decode_burst,
                      spec=self._spec_draft(model, now),
                      fault=(self.faults.injector(model, backend, incarnation)
                             if self.faults is not None else None),
                      obs=(self.obs.engine_obs(model, backend)
                           if self.obs is not None else None))
            if use_paged:
                eng = PagedInferenceEngine(cfg, params,
                                           BACKENDS[backend],
                                           block_size=self.block_size, **kw)
            else:
                eng = InferenceEngine(cfg, params, BACKENDS[backend], **kw)
            # trigger compile/execute of the step functions before the replica
            # counts as live (the dominant real cold-start cost when cold) —
            # with obs muted, so compile-bound probe steps never land in the
            # engine step-duration histograms
            probe_obs, eng._obs = eng._obs, None
            probe_fault, eng._fault = eng._fault, None   # probes aren't chaos targets
            eng.run([Request(uid=-1, tokens=[1, 2, 3],
                             sampling=SamplingParams(max_new_tokens=2))])
            eng._obs = probe_obs
            eng._fault = probe_fault
            eng.health = ReplicaHealth(since=now)
            eng.incarnation = incarnation
            # servelint: disable=SL001 -- real wall interval: spin-up duration
            dur = time.perf_counter() - t0
            reps.append(eng)
            entry = self.reg.entry(model, backend)
            entry.replicas = len(reps)
            entry.warm = 0
            kind = "spin-warm" if warm else "spin-cold"
            self.events.append(ScaleEvent(now, model, backend, len(reps) - 1,
                                          len(reps), kind, dur))
            self.cold_starts.append(
                (f"{model}/{backend}/{'warm' if warm else 'cold'}", dur))
            if self.obs is not None:
                self.obs.registry.histogram(
                    "cold_start_s" if not warm else "warm_start_s",
                    model).observe(dur)
                self.obs.events.append("scale", t=now, model=model,
                                       backend=backend, before=len(reps) - 1,
                                       after=len(reps), kind=kind,
                                       duration_s=dur)
                # open this replica's chip-second meter: the spin window
                # (param build + compile + probes) is COLD chip-seconds; the
                # metered clock starts now. perf_counter domain throughout —
                # the same clock engine.step() stamps with.
                eng._obs.meter = self.obs.ledger.replica_up(
                    model, backend, chips=entry.cost.chips, cold_s=dur,
                    t=time.perf_counter())  # servelint: disable=SL001 -- ledger is perf_counter domain (engine.step stamps feed it)
                self._update_memory_gauges(model, now)
                self._health_gauges(model)

    def _spin_down(self, model: str, backend: str, target: int,
                   now: float) -> None:
        key = (model, backend)
        reps = self._replicas[key]
        before = len(reps)
        # idle replicas retire immediately; BUSY excess drains instead
        # of being skipped (the old behavior) or killed: out of
        # placement now, stepped until in-flight work finishes, retired
        # by finish_drains() — deadline-bounded so a wedged request
        # can't pin a replica forever
        idle = [e for e in reps if not e.has_work()]
        excess = before - target
        for eng in idle[:max(0, excess)]:
            reps.remove(eng)
            self._settle_meter(eng)
        excess = len(reps) - target
        if excess > 0:
            # drain the least-loaded first: they free capacity soonest
            busy = sorted(reps, key=lambda e: e.pending_tokens())
            dr = self._draining.setdefault(key, [])
            for eng in busy[:excess]:
                reps.remove(eng)
                dr.append((eng, now + self.drain_deadline_s))
                self.events.append(ScaleEvent(now, model, backend,
                                              len(reps) + 1, len(reps),
                                              "drain", 0.0))
                if self.obs is not None:
                    self.obs.events.append("scale", t=now, model=model,
                                           backend=backend,
                                           before=len(reps) + 1,
                                           after=len(reps), kind="drain",
                                           duration_s=0.0)
        entry = self.reg.entry(model, backend)
        entry.replicas = len(reps)
        entry.warm = 1 if (not reps and model in self._params) else 0
        if len(reps) != before:
            kind = "zero" if not reps else "down"
            self.events.append(ScaleEvent(now, model, backend, before,
                                          len(reps), kind, 0.0))
            if self.obs is not None:
                self.obs.events.append("scale", t=now, model=model,
                                       backend=backend, before=before,
                                       after=len(reps), kind=kind,
                                       duration_s=0.0)
                self._update_memory_gauges(model, now)
                self._health_gauges(model)

    # -- fault tolerance: health, quarantine, repair, drain ---------------
    def _settle_meter(self, eng: InferenceEngine) -> None:
        """Close a retiring replica's chip-second meter exactly once —
        ``replica_down`` is idempotent, so the quarantine, drain and
        scale-down paths may all reach the same meter safely."""
        if (self.obs is not None and eng._obs is not None
                and eng._obs.meter is not None):
            self.obs.ledger.replica_down(
                eng._obs.meter,
                time.perf_counter())  # servelint: disable=SL001 -- ledger is perf_counter domain (engine.step stamps feed it)

    def _health_gauges(self, model: str) -> None:
        """Publish ``replica_health``: live replicas of ``model`` per
        health state (draining counted under their current state) plus
        the monotonic quarantined total."""
        if self.obs is None:
            return
        counts = {"healthy": 0, "degraded": 0}
        for b in self.reg.backends:
            for e in self.service_engines(model, b):
                h = getattr(e, "health", None)
                st = h.state if h is not None else "healthy"
                counts[st] = counts.get(st, 0) + 1
        counts["quarantined"] = self._model_quarantines.get(model, 0)
        for st, n in counts.items():
            self.obs.registry.gauge(
                "replica_health", f"{model}|state={st}").set(float(n))

    def note_step_ok(self, eng: InferenceEngine, now: float) -> None:
        """A clean step resets the circuit breaker (degraded -> healthy)."""
        h = getattr(eng, "health", None)
        if h is None or (h.consecutive_failures == 0
                         and h.state == "healthy"):
            return
        h.consecutive_failures = 0
        if h.state == "degraded":
            h.state = "healthy"
            h.since = now
            if eng._obs is not None:
                self._health_gauges(eng._obs.model)

    def report_step_failure(self, model: str, backend: str,
                            eng: InferenceEngine, exc: BaseException,
                            now: float):
        """Containment entry point for a step that raised. Counts the
        failure against the replica's breaker; returns the evacuated
        request list when the replica was quarantined (poisoned engines
        quarantine immediately — their host/device bookkeeping can no
        longer be trusted), else None (degraded; it keeps serving)."""
        h = getattr(eng, "health", None)
        if h is None:
            h = eng.health = ReplicaHealth(since=now)
        h.consecutive_failures += 1
        h.failures += 1
        h.last_error = repr(exc)
        if (getattr(eng, "poisoned", False)
                or h.consecutive_failures >= self.quarantine_after):
            return self.quarantine(model, backend, eng, now,
                                   reason=repr(exc))
        if h.state != "degraded":
            h.state = "degraded"
            h.since = now
            self._health_gauges(model)
        return None

    def quarantine(self, model: str, backend: str, eng: InferenceEngine,
                   now: float, reason: str = ""):
        """Remove a sick replica from service: evacuate its live
        requests (returned for resubmission), settle its cost meter,
        refresh the HBM/health gauges, and mark a substitute pending
        for the repair path. Idempotent per engine."""
        key = (model, backend)
        reps = self._replicas[key]
        found = False
        if eng in reps:
            reps.remove(eng)
            found = True
            self.reg.entry(model, backend).replicas = len(reps)
        else:
            dr = self._draining.get(key, [])
            for pair in dr:
                if pair[0] is eng:
                    dr.remove(pair)
                    found = True
                    break
        if not found:                     # already quarantined
            return []
        h = getattr(eng, "health", None)
        if h is None:
            h = eng.health = ReplicaHealth()
        h.state = "quarantined"
        h.since = now
        self.quarantines += 1
        self._model_quarantines[model] = \
            self._model_quarantines.get(model, 0) + 1
        evac = eng.evacuate()
        self._settle_meter(eng)
        self._pending_replace[key] = self._pending_replace.get(key, 0) + 1
        self.events.append(ScaleEvent(now, model, backend, len(reps) + 1,
                                      len(reps), "quarantine", 0.0))
        if self.obs is not None:
            self.obs.registry.counter("replicas_quarantined_total",
                                      model).inc()
            self.obs.events.append("quarantine", t=now, model=model,
                                   backend=backend,
                                   incarnation=getattr(eng, "incarnation",
                                                       -1),
                                   evacuated=len(evac), reason=reason)
            self._update_memory_gauges(model, now)
            self._health_gauges(model)
        return evac

    def replace_quarantined(self, now: Optional[float] = None
                            ) -> Dict[_Key, int]:
        """Repair path (called from the orchestrator tick): spin one
        substitute per pending quarantine — warm-pool aware, so a
        service whose params/code survived pays only the warm start.
        An injected spin failure leaves the replacement pending for the
        next tick. Returns {service: substitutes spun}."""
        now = time.perf_counter() if now is None else now
        done: Dict[_Key, int] = {}
        for key, n in list(self._pending_replace.items()):
            spun = 0
            for _ in range(n):
                try:
                    self._spin_up(key[0], key[1], now)
                    spun += 1
                except InjectedFault:
                    break                 # retry at the next tick
            if spun:
                left = n - spun
                if left > 0:
                    self._pending_replace[key] = left
                else:
                    del self._pending_replace[key]
                done[key] = spun
        return done

    def finish_drains(self, now: Optional[float] = None):
        """Retire draining replicas whose in-flight work finished; past
        the deadline, evacuate what's left so it can be resubmitted
        elsewhere (returned as ``[((model, backend), evac), ...]``)."""
        now = time.perf_counter() if now is None else now
        expired = []
        for key, dr in list(self._draining.items()):
            for eng, deadline in list(dr):
                started = deadline - self.drain_deadline_s
                if not eng.has_work():
                    dr.remove((eng, deadline))
                    self._retire_drained(key, eng, now, started, "drained")
                elif now >= deadline:
                    dr.remove((eng, deadline))
                    evac = eng.evacuate()
                    if evac:
                        expired.append((key, evac))
                    self._retire_drained(key, eng, now, started,
                                         "drain-timeout")
            if not dr:
                del self._draining[key]
        return expired

    def _retire_drained(self, key: _Key, eng: InferenceEngine, now: float,
                        started: float, kind: str) -> None:
        model, backend = key
        self._settle_meter(eng)
        n = len(self._replicas[key])
        self.events.append(ScaleEvent(now, model, backend, n + 1, n,
                                      kind, 0.0))
        if self.obs is not None:
            self.obs.registry.histogram(
                "drain_s", model,
                bounds=(0.01, 0.1, 0.5, 1.0, 5.0, 30.0)).observe(
                    max(0.0, now - started))
            self.obs.events.append("scale", t=now, model=model,
                                   backend=backend, before=n + 1, after=n,
                                   kind=kind, duration_s=0.0)
            self._update_memory_gauges(model, now)
            self._health_gauges(model)

    def _update_memory_gauges(self, model: str, now: float) -> None:
        """Refresh ``hbm_resident_bytes`` for ``model``: params + KV
        tensors summed over every live replica (all backends). Cheap —
        shape metadata only — and called on scale transitions, not per
        step.  Stamped with the caller's scale clock ``now`` so
        sim-clock drivers don't leak wall time into the gauge."""
        if self.obs is None:
            return
        total = float(sum(e.resident_bytes() for b in self.reg.backends
                          for e in self._replicas[(model, b)]))
        self.obs.registry.gauge("hbm_resident_bytes", model).set(
            total, stamp=now)

    def kv_bytes(self, model: str) -> Optional[Tuple[int, int]]:
        """(used, free) KV-pool bytes over every live replica of
        ``model``; None with no live replicas."""
        reps = [e for b in self.reg.backends
                for e in self._replicas[(model, b)]]
        if not reps:
            return None
        pairs = [e.kv_pool_bytes() for e in reps]
        return sum(u for u, _ in pairs), sum(f for _, f in pairs)
