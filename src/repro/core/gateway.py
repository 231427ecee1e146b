"""The Pick-and-Spin gateway — one serving API over the real serve plane.

``ServeFrontend`` is the single entry point (serving API v2): every
request — from the synchronous ``Gateway`` facade, the open-loop driver,
launchers, examples and benchmarks — takes the SAME path:

    CompletionRequest -> Router -> Algorithm-2 policy -> priority-ordered
    bounded admission queue (RequestScheduler) -> ReplicaPool of real
    engines, with Algorithm 1 (``Orchestrator.tick``) running inline
    against LIVE telemetry.

``submit()`` returns a ``CompletionHandle`` immediately: ``.result()``
drives the serve loop to completion, ``.tokens()`` streams one event per
decode iteration, ``.cancel()`` aborts queued or mid-decode work (slot +
KV blocks freed the same call). Shed requests resolve with a structured
``finish_reason == "shed"`` — never ``None``. Requests carrying a
``session_id`` chain multi-turn: the frontend prepends the session's
token history, which is exactly the prefix the paged engines' radix
cache holds, so turn N+1 prefills only its new suffix.

Model "spin-up" here is genuinely expensive (param init/load + XLA
compile), so cold starts, warm pools and scale-to-zero are measured, not
modeled — each response's ``usage.cold_start_s`` carries the spin time
the request actually waited on. This is the calibration source for the
simulator's constants on small archs, and the end-to-end serving
substrate.
"""
from __future__ import annotations

import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple, Union

from repro.api import (CompletionHandle, CompletionRequest,
                       CompletionResponse, FinishReason, Priority, Usage)
from repro.configs.base import ModelConfig
from repro.core.orchestrator import Orchestrator, SpinConfig
from repro.core.policies import MultiObjectivePolicy, SelectionPolicy
from repro.core.registry import ServiceRegistry
from repro.core.router import KeywordRouter
from repro.core.scoring import PROFILES, OperatorProfile
from repro.core.telemetry import Telemetry
from repro.data.tokenizer import ByteTokenizer
from repro.obs import Observability, phase
from repro.serving import (GenResult, ReplicaPool, Request, RequestScheduler,
                           SamplingParams, SchedulerConfig, SpecConfig)


@dataclass
class GatewayConfig:
    """The ONE construction recipe for a serve plane. Both ``Gateway``
    and ``ServeFrontend`` build from it, so there is a single
    registry/policy/router setup path.

    ``models`` are what EXECUTES (reduced on CPU); ``cost_configs``
    (default: the full assigned configs with the same names) drive the
    registry's production cost model, so tier economics — the reason
    Pick exists — stay realistic even when stand-in models serve."""
    models: Dict[str, ModelConfig]
    router: object = None                      # default: KeywordRouter()
    policy_cls: type = MultiObjectivePolicy
    profile: OperatorProfile = PROFILES["balanced"]
    backends: Tuple[str, ...] = ("trt",)
    max_seq: int = 256
    seed: int = 0
    cost_configs: Optional[Dict[str, ModelConfig]] = None
    spin: Optional[SpinConfig] = None
    sched: Optional[SchedulerConfig] = None
    paged: object = "auto"
    # continuous batching: each engine step spends `step_token_budget`
    # tokens — one per in-flight decode first, the rest on prefill
    # chunks of at most `chunk_tokens` — so a long prompt amortizes
    # across steps instead of stalling every in-flight decode.
    # chunk_tokens=None restores whole-prompt prefill (the bench
    # baseline); budget=None leaves the step unbounded.
    chunk_tokens: Optional[int] = 64
    step_token_budget: Optional[int] = 256
    # opt-in decode burst: with no prefill backlog pending, one engine
    # step runs K fused decode iterations in a single device dispatch
    # (the offline/throughput path). 1 keeps stepwise decoding — the
    # right default for an interactive serve plane, where bursts delay
    # admission of freshly arrived prompts by up to K-1 decode tokens.
    decode_burst: int = 1
    # speculative decoding: registry arch that drafts spec_k tokens per
    # verify on every engine whose target it can co-reside with (vocab
    # match + KV headroom; others fall back to plain fused stepwise).
    # None keeps spec off pool-wide.
    spec_draft: Optional[str] = None
    spec_k: int = 4
    autoscale: bool = True                     # run Algorithm 1 inline
    # observability plane: metrics registry + request tracing + event
    # log, shared by the scheduler, the pool and every spun engine. All
    # hooks are host-side bookkeeping on code paths that already ran —
    # zero new device->host syncs (the PR-5 transfer-guard contract
    # holds with metrics on), so the default is on.
    metrics: bool = True
    # anomaly flight recorder JSONL sink: when set, automatic dumps
    # (shed storm / expiry burst / engine exception) and on-demand
    # ``obs.flight.dump()`` calls append there. None keeps the ring
    # in-memory only.
    flight_record: Optional[str] = None
    result_retention: int = 256                # bounded finished-result buffer
    session_retention: int = 1024              # LRU bound on live sessions
    # fault tolerance: a seeded ``FaultPlan`` (serving.faults) injected
    # into every spun replica; the circuit-breaker threshold before a
    # failing replica is quarantined; and how long a draining replica
    # may run out its in-flight work before forced evacuation
    faults: Optional[object] = None            # serving.faults.FaultPlan
    quarantine_after: int = 2
    drain_deadline_s: float = 30.0

    def resolved_cost_configs(self) -> Dict[str, ModelConfig]:
        from repro.configs.registry import ARCHS as _FULL
        return self.cost_configs or {
            name: _FULL.get(name.replace("-smoke", ""), cfg)
            for name, cfg in self.models.items()}


@dataclass
class OrchEvent:
    """An Algorithm-1 decision applied to live engines."""
    t: float
    model: str
    before: int          # replicas before the tick
    target: int          # replica target the orchestrator issued

    @property
    def kind(self) -> str:
        if self.target == 0:
            return "scale-to-zero"
        if self.target > self.before:
            return "scale-up"
        return "hold" if self.target == self.before else "scale-down"

    def __str__(self) -> str:
        return (f"[tick] {self.kind:>13s} {self.model} "
                f"{self.before}->{self.target}")


@dataclass
class _Session:
    """Multi-turn chain: service pinned on the first turn (history
    tokens only mean something to one model), token history grown on
    each completed turn. Turn N+1's prompt = history + new text, which
    is the prefix the radix cache registered when turn N finished.

    Turns are sequential by contract (submit turn N+1 after turn N
    resolves). An overlapping turn is still served, but it neither sees
    nor overwrites history it wasn't built on — the ``turns`` counter
    guards the chain against clobbering."""
    model: str
    backend: str
    tier: str
    tokens: List[int] = field(default_factory=list)
    turns: int = 0


@dataclass
class _Inflight:
    request: CompletionRequest
    ereq: Request
    model: str
    backend: str
    tier: str
    cold_mark: int       # len(pool.cold_starts) at submit, for attribution
    turn: int = -1       # session turn counter at submit (-1: no session)


class ServeFrontend:
    """Serving API v2 frontend: typed submit -> handle, step-driven serve
    loop, streaming deltas, cancellation, sessions, priorities."""

    def __init__(self, models_or_config: Union[GatewayConfig,
                                               Dict[str, ModelConfig]],
                 **kw):
        cfg = (models_or_config if isinstance(models_or_config, GatewayConfig)
               else GatewayConfig(models=models_or_config, **kw))
        self.config = cfg
        self.models = cfg.models
        self.router = cfg.router or KeywordRouter()
        self.registry = ServiceRegistry(cfg.resolved_cost_configs(),
                                        cfg.backends)
        # scale-from-zero on route: cold start priced into the prediction
        self.policy: SelectionPolicy = cfg.policy_cls(
            self.registry, cfg.seed, require_capacity=False)
        self.profile = cfg.profile
        self.obs = Observability() if cfg.metrics else None
        if self.obs is not None and cfg.flight_record:
            self.obs.flight.config.path = cfg.flight_record
        self.telemetry = Telemetry(
            registry=self.obs.registry if self.obs is not None else None)
        self.tok = ByteTokenizer()
        self.max_seq = cfg.max_seq
        self.spin = cfg.spin or SpinConfig()
        self.pool = ReplicaPool(cfg.models, self.registry, max_seq=cfg.max_seq,
                                seed=cfg.seed, paged=cfg.paged,
                                chunk_tokens=cfg.chunk_tokens,
                                step_token_budget=cfg.step_token_budget,
                                decode_burst=cfg.decode_burst, obs=self.obs,
                                spec=(SpecConfig(cfg.spec_draft, cfg.spec_k)
                                      if cfg.spec_draft else None),
                                faults=cfg.faults,
                                quarantine_after=cfg.quarantine_after,
                                drain_deadline_s=cfg.drain_deadline_s)
        self.scheduler = RequestScheduler(self.pool, self.registry,
                                          self.telemetry, cfg.sched,
                                          obs=self.obs)
        self.orch = Orchestrator(self.registry, self.telemetry, self.spin,
                                 scale_cb=self.pool.scale,
                                 repair_cb=self.pool.replace_quarantined)
        self.orch_events: List[OrchEvent] = []
        self._next_tick = 0.0
        self._uid = 0
        self._inflight: Dict[int, _Inflight] = {}
        self._handles: Dict[int, CompletionHandle] = {}
        # bounded retention of finished responses (a serve plane driven
        # via serve_all()/step() without claiming handles must not grow
        # without bound) — drain() hands them over explicitly
        self._recent: "OrderedDict[int, CompletionResponse]" = OrderedDict()
        # LRU-bounded: one-shot conversations with unique ids must not
        # accumulate forever on a long-running plane (end_session() is
        # the explicit path; the bound is the backstop)
        self._sessions: "OrderedDict[str, _Session]" = OrderedDict()

    @property
    def cold_starts(self) -> List[Tuple[str, float]]:
        return self.pool.cold_starts

    # -- request path ("Pick" -> enqueue) ------------------------------------
    def submit(self, request: Union[CompletionRequest, str], *,
               max_new_tokens: int = 16, deadline_s: Optional[float] = None,
               priority: Priority = Priority.NORMAL,
               session_id: Optional[str] = None,
               sampling: Optional[SamplingParams] = None) -> CompletionHandle:
        """Route + select + enqueue. ALWAYS returns a handle: a shed
        request's handle is already resolved with ``finish_reason ==
        "shed"`` (structured backpressure, not ``None``)."""
        with phase("gateway.submit"):
            if not isinstance(request, CompletionRequest):
                request = CompletionRequest(
                    prompt=request, max_new_tokens=max_new_tokens,
                    deadline_s=deadline_s, priority=priority,
                    session_id=session_id, sampling=sampling)
            now = time.perf_counter()
            prompt_tokens = self.tok.encode(request.prompt)
            sess = (self._sessions.get(request.session_id)
                    if request.session_id else None)
            if sess is None:
                decision = self.router.route(request.prompt)
                sel = self.policy.select(decision, len(prompt_tokens),
                                         request.max_new_tokens, self.profile)
                model, backend = sel.entry.model, sel.entry.backend
                tier = sel.entry.tier
                if request.session_id:      # pin the service for later turns
                    sess = _Session(model, backend, tier)
                    self._sessions[request.session_id] = sess
                    self._bound_sessions()
            else:
                model, backend, tier = sess.model, sess.backend, sess.tier
                self._sessions.move_to_end(request.session_id)
            self.telemetry.record_request(model, now)
            cfg = self.models[model]
            tokens = [t % cfg.vocab_size for t in prompt_tokens]
            if sess is not None:
                tokens = sess.tokens + tokens
            uid = self._uid
            self._uid += 1
            if self.obs is not None:
                self.obs.tracer.on_submit(uid, model, backend, now)
            ereq = Request(uid=uid, arrival_t=now, tokens=tokens,
                           sampling=request.sampling or
                           SamplingParams(max_new_tokens=request.max_new_tokens),
                           deadline_s=request.deadline_s,
                           priority=int(request.priority))
            handle = CompletionHandle(self, uid, request, model=model,
                                      backend=backend, tier=tier)
            info = _Inflight(request, ereq, model, backend, tier,
                             cold_mark=len(self.pool.cold_starts),
                             turn=sess.turns if sess is not None else -1)
            if not self.scheduler.enqueue(model, backend, ereq, now):
                res = GenResult(uid=uid, prompt_len=len(tokens), shed=True)
                handle._resolve(self._make_response(info, res))
                self._remember(handle.response)
                return handle
            self._inflight[uid] = info
            self._handles[uid] = handle
            return handle

    # -- serve loop -----------------------------------------------------
    def step(self) -> List[CompletionResponse]:
        """One serve-loop iteration: Algorithm-1 tick when due, one
        scheduling + decode pass over the pool, streaming deltas pushed
        to their handles. Returns newly finished responses."""
        with phase("gateway.step"):
            now = time.perf_counter()
            # replace quarantined replicas at STEP cadence, not tick cadence:
            # a substitute owed between widely spaced Algorithm-1 ticks (or
            # with autoscale off) must not wait for one. Idempotent with the
            # tick's own repair path.
            self.pool.replace_quarantined(now)
            if self.config.autoscale and now >= self._next_tick:
                before = {m: self.registry.model_replicas(m)
                          for m in self.registry.models}
                for m, target in self.orch.tick(now).items():
                    ev = OrchEvent(now, m, before[m], target)
                    self.orch_events.append(ev)
                    if self.obs is not None:
                        self.obs.events.append("orch", t=now, model=m,
                                               before=ev.before,
                                               target=ev.target, kind=ev.kind)
                self._next_tick = now + self.spin.tick_s
            finished = self.scheduler.step(now)
            out: List[CompletionResponse] = []
            with phase("gateway.deliver"):
                for uid, token in self.scheduler.drain_deltas():
                    h = self._handles.get(uid)
                    if h is not None:        # warm-up probes have no handle
                        h._push_token(token)
                for _key, res in finished:
                    resp = self._finish(res)
                    if resp is not None:
                        out.append(resp)
            return out

    def cancel(self, uid: int) -> bool:
        """Abort ``uid`` wherever it is (queue or mid-decode). The handle
        resolves immediately with ``finish_reason == "cancelled"`` and
        the engine's slot + KV blocks are freed. False if unknown or
        already finished."""
        info = self._inflight.get(uid)
        if info is None:
            return False
        res = self.scheduler.cancel(info.model, info.backend, uid)
        if res is None:
            return False
        self._finish(res)
        return True

    def has_work(self) -> bool:
        return self.scheduler.has_work()

    def serve_all(self, max_steps: int = 1_000_000
                  ) -> List[CompletionResponse]:
        """Synchronous driver: run the serve loop until all queues drain."""
        out: List[CompletionResponse] = []
        steps = 0
        while self.has_work() and steps < max_steps:
            out.extend(self.step())
            steps += 1
        return out

    def drain(self) -> List[CompletionResponse]:
        """Hand over (and clear) the bounded buffer of finished
        responses — the explicit bulk-results surface for drivers that
        don't keep per-request handles."""
        out = list(self._recent.values())
        self._recent.clear()
        return out

    def serve_open_loop(self, requests: Sequence[CompletionRequest],
                        arrivals: Sequence[float]
                        ) -> Tuple[List[CompletionHandle], float]:
        """Open-loop driver: submit ``requests[i]`` at offset
        ``arrivals[i]`` (seconds, sorted) regardless of completions —
        arrivals do not wait for the system, so overload shows up as
        queueing/shedding, not as a slower workload. Drives the serve
        loop continuously in between. Returns (handles, wall_s); every
        handle is resolved on return (shed ones with
        ``finish_reason == "shed"``)."""
        t0 = time.perf_counter()
        handles: List[CompletionHandle] = []
        i, n = 0, len(requests)
        while i < n or self.has_work():
            now = time.perf_counter() - t0
            while i < n and arrivals[i] <= now:
                handles.append(self.submit(requests[i]))
                i += 1
            self.step()
            if not self.has_work() and i < n:
                time.sleep(max(0.0, min(0.005, arrivals[i] - now)))
        return handles, time.perf_counter() - t0

    def settle(self, timeout_s: float = 5.0, poll_s: float = 0.02) -> bool:
        """Idle the serve loop so Spin's idle branch can fire (scale-to-
        zero / warm floors). True once no replicas above the configured
        warm floors remain live."""
        floor = self._floor_replicas()
        t_end = time.perf_counter() + timeout_s
        while time.perf_counter() < t_end:
            self.step()
            if self.pool.total_replicas() <= floor:
                return True
            time.sleep(poll_s)
        return self.pool.total_replicas() <= floor

    def end_session(self, session_id: str) -> bool:
        """Drop a session's history explicitly (its cached KV blocks age
        out of the radix cache on their own). True if it existed."""
        return self._sessions.pop(session_id, None) is not None

    # -- internals -------------------------------------------------------
    def _finish(self, res: GenResult) -> Optional[CompletionResponse]:
        info = self._inflight.pop(res.uid, None)
        handle = self._handles.pop(res.uid, None)
        if info is None:                 # warm-up probe etc.
            return None
        resp = self._make_response(info, res)
        if info.request.session_id and resp.completed:
            sess = self._sessions.get(info.request.session_id)
            # turn guard: only a turn built on the CURRENT history may
            # extend it — an overlapping turn (submitted before the
            # previous one resolved) is served but never clobbers the
            # chain with history it didn't see
            if sess is not None and sess.turns == info.turn:
                sess.tokens = info.ereq.tokens + res.new_tokens
                sess.turns += 1
        if handle is not None:
            handle._resolve(resp)
        self._remember(resp)
        return resp

    def _make_response(self, info: _Inflight,
                       res: GenResult) -> CompletionResponse:
        if res.shed:
            reason = FinishReason.SHED
        elif res.cancelled:
            reason = FinishReason.CANCELLED
        elif res.failed:
            reason = FinishReason.FAILED
        elif res.timed_out:
            reason = FinishReason.TIMEOUT
        else:
            eos = info.ereq.sampling.eos_id
            reason = (FinishReason.STOP if res.completed and eos is not None
                      and res.new_tokens and res.new_tokens[-1] == eos
                      else FinishReason.LENGTH)
        # real measured spin time this request waited on: every cold/warm
        # start of ITS service logged between submit and finish
        svc = f"{info.model}/{info.backend}/"
        cold = sum(d for label, d in
                   self.pool.cold_starts[info.cold_mark:]
                   if label.startswith(svc))
        # every terminal resolution passes through here exactly once
        # (shed-at-submit included), so this is where the span closes
        span = (self.obs.tracer.on_finish(res.uid, time.perf_counter(),
                                          reason)
                if self.obs is not None else None)
        # settle the chip-second ledger: the request's attributed share
        # becomes its measured cost (None = it never shared a step)
        chip_s = cost_usd = 0.0
        if self.obs is not None:
            closed = self.obs.ledger.close_request(
                res.uid, info.model,
                t=span.finish_t if span else time.perf_counter())
            if closed is not None:
                chip_s, cost_usd = closed
            if span is not None:
                span.chip_seconds, span.cost_usd = chip_s, cost_usd
        usage = Usage(prompt_tokens=res.prompt_len,
                      cached_tokens=res.cached_tokens,
                      completion_tokens=len(res.new_tokens),
                      cold_start_s=cold,
                      prefill_chunks=res.prefill_chunks,
                      queue_wait_s=span.queue_wait_s if span else 0.0,
                      decode_s=span.decode_s if span else 0.0,
                      chip_seconds=chip_s, cost_usd=cost_usd,
                      kv_peak_bytes=res.kv_bytes,
                      drafted_tokens=res.drafted_tokens,
                      accepted_tokens=res.accepted_tokens,
                      retries=res.retries)
        return CompletionResponse(
            uid=res.uid, prompt=info.request.prompt, model=info.model,
            backend=info.backend, tier=info.tier,
            new_tokens=list(res.new_tokens), finish_reason=reason,
            completed=res.completed, ttft_s=res.ttft, latency_s=res.latency,
            usage=usage, session_id=info.request.session_id)

    def _remember(self, resp: CompletionResponse) -> None:
        self._recent[resp.uid] = resp
        while len(self._recent) > self.config.result_retention:
            self._recent.popitem(last=False)

    def _bound_sessions(self) -> None:
        while len(self._sessions) > self.config.session_retention:
            self._sessions.popitem(last=False)

    def _floor_replicas(self) -> int:
        """Total replicas Spin's idle branch would leave running."""
        total = 0
        for m in self.registry.models:
            tier = next(e.tier for e in self.registry.entries()
                        if e.model == m)
            floor = self.spin.warm_pool.get(tier, 0)
            if not self.spin.scale_to_zero:
                floor = max(1, floor)
            total += floor
        return total


class Gateway:
    """Thin SYNCHRONOUS facade over ``ServeFrontend`` — the serial
    baseline (one blocking request at a time) with zero construction
    logic of its own. ``handle()`` is ``submit().result()`` on the same
    concurrent plane everything else uses; Algorithm-1 autoscaling is
    off (the caller drives lifecycle explicitly via ``scale_to_zero``)."""

    def __init__(self, models: Dict[str, ModelConfig], router=None,
                 policy_cls=MultiObjectivePolicy,
                 profile: OperatorProfile = PROFILES["balanced"],
                 backends: Tuple[str, ...] = ("trt",),
                 max_seq: int = 256, seed: int = 0,
                 cost_configs: Optional[Dict[str, ModelConfig]] = None,
                 sched: Optional[SchedulerConfig] = None, paged="auto",
                 chunk_tokens: Optional[int] = 64,
                 step_token_budget: Optional[int] = 256,
                 decode_burst: int = 1, spec_draft: Optional[str] = None,
                 spec_k: int = 4):
        self.frontend = ServeFrontend(GatewayConfig(
            models=models, router=router, policy_cls=policy_cls,
            profile=profile, backends=backends, max_seq=max_seq, seed=seed,
            cost_configs=cost_configs, sched=sched, paged=paged,
            chunk_tokens=chunk_tokens, step_token_budget=step_token_budget,
            decode_burst=decode_burst, spec_draft=spec_draft, spec_k=spec_k,
            autoscale=False))

    # shared-plane passthroughs (no duplicated state)
    models = property(lambda self: self.frontend.models)
    router = property(lambda self: self.frontend.router)
    registry = property(lambda self: self.frontend.registry)
    policy = property(lambda self: self.frontend.policy)
    profile = property(lambda self: self.frontend.profile)
    telemetry = property(lambda self: self.frontend.telemetry)
    tok = property(lambda self: self.frontend.tok)
    max_seq = property(lambda self: self.frontend.max_seq)
    pool = property(lambda self: self.frontend.pool)
    scheduler = property(lambda self: self.frontend.scheduler)
    cold_starts = property(lambda self: self.frontend.cold_starts)
    obs = property(lambda self: self.frontend.obs)

    # -- request path ("Pick" -> serve) -------------------------------------
    def handle(self, text: str, max_new_tokens: int = 16,
               deadline_s: Optional[float] = None,
               priority: Priority = Priority.NORMAL,
               session_id: Optional[str] = None,
               sampling: Optional[SamplingParams] = None
               ) -> CompletionResponse:
        return self.frontend.submit(
            text, max_new_tokens=max_new_tokens, deadline_s=deadline_s,
            priority=priority, session_id=session_id,
            sampling=sampling).result()

    # -- lifecycle ("Spin", explicit on the serial facade) -------------------
    def scale_to_zero(self, model: str, backend: str,
                      keep_warm: bool = True) -> None:
        self.frontend.pool.scale(model, backend, 0)
        if not keep_warm:
            self.frontend.pool.evict(model)
