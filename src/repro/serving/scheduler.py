"""Request scheduler: bounded admission queues + the step-driven serve loop.

Sits between the Pick layer (router + Algorithm-2 policy, which choose a
(model, backend) service per request) and the ``ReplicaPool`` of real
engines. Responsibilities:

  * per-service admission queues with a bounded depth measured in BOTH
    requests and TOKENS (``max_queue_tokens``): one 8k-token prompt
    loads a queue like hundreds of chat turns, so counting requests
    alone hides the backlog that actually determines waiting time under
    chunked prefill. Beyond either bound requests are SHED at admission
    (backpressure instead of unbounded latency collapse). Queues are
    PRIORITY-ordered: dispatch serves the highest priority class first
    (FIFO within a class), and under pressure a full queue sheds
    strictly low-before-high — an arriving high-priority request evicts
    the newest queued request of the lowest class rather than being
    rejected. Every shed is a structured result (``GenResult.shed``)
    delivered through the serve loop, never a silent drop;
  * deadline-aware dispatch: queued requests already past their deadline
    are dropped before ever touching an engine slot;
  * cancellation: ``cancel()`` aborts a request wherever it lives —
    still queued (removed before touching a slot) or mid-decode (the
    engine frees its slot and KV blocks the same call);
  * scale-from-zero on demand: work queued on a service with no live
    replicas spins one up (the Orchestrator adds capacity beyond that);
  * the serve loop: ``step()`` admits queued work into free slots (least
    loaded replica first) and runs ONE decode iteration on every engine
    with work — iteration-level continuous batching across the whole
    pool, so many requests genuinely overlap;
  * KV-cache awareness (paged services): queued requests with the
    largest cached-prefix reuse are dispatched first (they prefill the
    least and free their slot soonest), placement prefers the replica
    whose radix cache holds the request's prefix, and a block-watermark
    shed policy tightens the admission queue when the pool runs dry —
    backpressure arrives BEFORE the engines thrash on eviction.

The scheduler also keeps the registry's ``queued``/``active_requests``
live and reports finish latencies plus KV pool occupancy / prefix
hit-rate gauges to telemetry, which is exactly what Algorithm 1 reads on
each tick.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field, replace
from typing import Deque, Dict, List, Optional, Tuple

from repro.obs.trace import phase
from repro.serving.engine import GenResult, Request
from repro.serving.replica_pool import ReplicaPool

_Key = Tuple[str, str]


@dataclass
class SchedulerConfig:
    max_queue_depth: int = 64     # per-service bound; beyond this we shed
    # per-service queue bound in TOKENS (prompt tokens waiting to
    # prefill) — the request bound's blind spot. None disables.
    max_queue_tokens: Optional[int] = 16384
    shed_expired: bool = True     # drop queued requests already past deadline
    spin_on_demand: bool = True   # scale 0->1 when work queues on a dead svc
    prefix_aware: bool = True     # dispatch best-cached-prefix first
    block_watermark: float = 0.05  # free-block frac below which we shed early
    watermark_depth_div: int = 8  # queue depth divisor under block pressure
    # fault containment: a replica whose step raises is reported to the
    # pool's circuit breaker (degrade -> quarantine) and its in-flight
    # requests are RESUBMITTED — deterministic retry. False re-raises
    # (the chaos bench's no-containment baseline).
    contain_failures: bool = True
    max_retries: int = 3          # resubmissions per request before FAILED
    retry_backoff_s: float = 0.0  # linear backoff between resubmissions
    # "replay" re-runs the ORIGINAL request (same uid -> same per-request
    # PRNG stream, same served prompt -> bit-identical computation) and
    # suppresses the already-delivered stream deltas: token-for-token
    # identical to the unfailed run BY CONSTRUCTION, greedy and seeded
    # stochastic alike. "chain" instead prefills the emitted tokens onto
    # the prompt (the session-chaining trick) and resumes the PRNG draw
    # counter past them — cheaper (no re-decode), and exact whenever the
    # chained KV is served verbatim from the prefix cache; but KV it
    # must RECOMPUTE goes through prefill under different bucket shapes
    # than the baseline's decode steps, and that numeric drift can flip
    # a near-tie for stochastic sampling (greedy argmax is robust in
    # practice). The determinism guarantee is only unconditional under
    # "replay", so that is the default.
    retry_mode: str = "replay"    # "replay" | "chain"


@dataclass
class SchedStats:
    submitted: int = 0
    shed: int = 0                 # rejected/evicted at admission
    shed_blocks: int = 0          # ...of which under KV block pressure
    shed_tokens: int = 0          # ...of which over the token bound
    preempted: int = 0            # ...of which queued low-priority evictions
    expired: int = 0              # dropped from queue past deadline
    cancelled: int = 0            # aborted by the caller
    dispatched: int = 0
    completed: int = 0
    steps: int = 0
    retries: int = 0              # containment resubmissions
    quarantines: int = 0          # replicas quarantined via this scheduler
    failed: int = 0               # retry budget exhausted


@dataclass
class _RetryCtx:
    """Per-uid retry bookkeeping. ``prior`` is the longest token run
    already DELIVERED to the caller across attempts (replay mode) or the
    accumulated chain (chain mode); ``prompt_len0`` is the original
    served prompt length (chain mode grows the request's tokens);
    ``to_skip`` counts stream deltas the current replay attempt must
    suppress — the caller already received them before the failure."""
    prior: List[int]
    retries: int
    prompt_len0: int
    to_skip: int = 0


class RequestScheduler:
    def __init__(self, pool: ReplicaPool, registry, telemetry,
                 cfg: Optional[SchedulerConfig] = None, obs=None):
        self.pool = pool
        self.reg = registry
        self.tel = telemetry
        self.cfg = cfg or SchedulerConfig()
        self._obs = obs               # Observability bundle (optional)
        self._queues: Dict[_Key, Deque[Request]] = {
            key: deque() for key in pool._replicas}
        # requests resolved OFF the engines (deadline-expired, priority-
        # evicted): surfaced as structured results on the next step
        self._reaped: List[Tuple[_Key, GenResult]] = []
        # (uid, token) streaming increments of the latest step
        self._deltas: List[Tuple[int, int]] = []
        # uid -> retry bookkeeping for requests resubmitted after a
        # replica failure (popped when the final result flushes)
        self._retry_ctx: Dict[int, _RetryCtx] = {}
        self.stats = SchedStats()

    def _note(self, event: str, model: str, now: Optional[float],
              **fields) -> None:
        """Structured decision record: every shed / preempt / expire /
        cancel lands in the event log AND a per-model counter, so
        control-loop behavior is reconstructable after the fact."""
        if self._obs is None:
            return
        self._obs.registry.counter("sched_" + event, model).inc()
        self._obs.events.append(event, t=now, model=model, **fields)

    # -- admission ----------------------------------------------------------
    def enqueue(self, model: str, backend: str, req: Request,
                now: Optional[float] = None) -> bool:
        """Admit a routed request. Returns False if shed (queue full and
        nothing of lower priority to evict). When the queue is full but
        holds a LOWER-priority request, that one is evicted instead
        (shed low before high) and surfaced as a ``shed`` result."""
        key = (model, backend)
        q = self._queues[key]
        self.stats.submitted += 1
        # resolve the clock ONCE, up front: a shed below this point must
        # log the caller's (possibly simulated) timestamp, not a stray
        # perf_counter interleaved into sim time (the PR-6 bug class)
        now = time.perf_counter() if now is None else now
        # fast path: nothing waiting and a free slot -> straight in
        if not q and self.pool.free_slots(model, backend) > 0:
            self._to_engine(key, req, now)
            self.stats.dispatched += 1
            self._flight_admit(False, now)
            return True
        over_tokens = (self.cfg.max_queue_tokens is not None and q and
                       self._queue_tokens(q) + self._req_tokens(req)
                       > self._token_limit(model, backend))
        if len(q) >= self._depth_limit(model, backend) or over_tokens:
            victims = self._shed_victims(model, backend, q, req)
            if victims is None:
                self.stats.shed += 1
                reason = "queue_full"
                if over_tokens:
                    self.stats.shed_tokens += 1
                    reason = "queue_tokens"
                # block-pressure shed = the TIGHTENED bound did it (an
                # ordinary queue-full shed at max depth is not the pool's)
                elif len(q) < self.cfg.max_queue_depth:
                    self.stats.shed_blocks += 1
                    reason = "block_pressure"
                self._note("shed", model, now, uid=req.uid, reason=reason)
                self._flight_admit(True, now)
                return False
            entry = self.reg.entry(model, backend)
            for victim in victims:
                q.remove(victim)
                res = GenResult(uid=victim.uid,
                                prompt_len=len(victim.tokens), shed=True)
                res.latency = now - victim.arrival_t
                self._reaped.append((key, res))
                self.stats.shed += 1
                self.stats.preempted += 1
                self._note("preempt", model, now, uid=victim.uid,
                           by=req.uid)
            q.append(req)
            entry.queued = max(0, entry.queued - len(victims) + 1)
            self._flight_admit(False, now)
            return True
        q.append(req)
        self.reg.entry(model, backend).queued += 1
        self._flight_admit(False, now)
        return True

    def _flight_admit(self, shed: bool, now: float) -> None:
        """Feed the flight recorder's shed-storm trigger."""
        if self._obs is not None and self._obs.flight is not None:
            self._obs.flight.note_admission(shed, now)

    def _shed_victims(self, model: str, backend: str, q: Deque[Request],
                      req: Request) -> Optional[List[Request]]:
        """Queued requests of STRICTLY lower priority classes whose
        eviction makes room for ``req`` under BOTH bounds — lowest class
        first, newest first within a class (FIFO fairness: equal
        priority never preempts). One victim frees a seat; the token
        bound may need several (one 8k prompt displaces many chat
        turns). None when no such set exists — then the ARRIVAL is shed
        and nobody already queued is punished for an infeasible one."""
        cands = [r for r in q if r.priority < req.priority]
        if not cands:
            return None
        cands.sort(key=lambda r: (r.priority, -r.arrival_t))
        token_limit = (self._token_limit(model, backend)
                       if self.cfg.max_queue_tokens is not None else None)
        depth = self._depth_limit(model, backend)
        tokens = self._queue_tokens(q)
        arriving = self._req_tokens(req)
        victims: List[Request] = []
        for r in cands:
            seat_ok = len(q) - len(victims) < depth
            tokens_ok = (token_limit is None
                         or tokens + arriving <= token_limit)
            if seat_ok and tokens_ok:
                return victims
            victims.append(r)
            tokens -= self._req_tokens(r)
        seat_ok = len(q) - len(victims) < depth
        tokens_ok = (token_limit is None
                     or tokens + arriving <= token_limit)
        return victims if seat_ok and tokens_ok else None

    def _under_block_pressure(self, model: str, backend: str) -> bool:
        """True when a paged service's pool is below the free-block
        watermark AND blocks (not slots) are the binding resource —
        compute idle, pool dry. A busy-slots busy-pool burst is ordinary
        queueing, not block starvation."""
        return (self.pool.kv_free_frac(model, backend)
                < self.cfg.block_watermark
                and self.pool.kv_bound(model, backend))

    def _depth_limit(self, model: str, backend: str) -> int:
        """Block-watermark shed policy: under block pressure, queued work
        would only sit behind block-starved admission. Tighten the queue
        bound so callers see backpressure now instead of latency collapse
        later."""
        depth = self.cfg.max_queue_depth
        if self._under_block_pressure(model, backend):
            depth = max(1, depth // self.cfg.watermark_depth_div)
        return depth

    def _token_limit(self, model: str, backend: str) -> int:
        """Token-denominated queue bound, tightened by the same
        watermark divisor under block pressure."""
        limit = self.cfg.max_queue_tokens
        if self._under_block_pressure(model, backend):
            limit = max(1, limit // self.cfg.watermark_depth_div)
        return limit

    def _req_tokens(self, r: Request) -> int:
        """Prompt tokens the engine will actually prefill: engines keep
        only the last ``max_seq - budget - 1`` tokens (budget = decode
        tokens still owed, which shrinks on retries whose emitted chain
        rides in the prompt), so counting a raw oversized prompt would
        shed real work over phantom load."""
        budget = max(r.sampling.max_new_tokens - r.prefix_draws, 1)
        return min(len(r.tokens), max(self.pool.max_seq - budget - 1, 1))

    def _queue_tokens(self, q: Deque[Request]) -> int:
        return sum(self._req_tokens(r) for r in q)

    def queued(self) -> int:
        return sum(len(q) for q in self._queues.values())

    def queued_tokens(self) -> int:
        """Total prompt tokens waiting in admission queues — queue depth
        in the unit that predicts prefill work, not request count."""
        return sum(self._queue_tokens(q) for q in self._queues.values())

    def has_work(self) -> bool:
        return (any(self._queues.values()) or bool(self._reaped)
                or any(eng.has_work() for _, eng in self.pool.engines()))

    # -- cancellation ---------------------------------------------------
    def cancel(self, model: str, backend: str, uid: int,
               now: Optional[float] = None) -> Optional[GenResult]:
        """Abort ``uid`` on the given service: removed from the admission
        queue, or cancelled mid-flight on whichever replica holds it
        (slot + KV blocks freed immediately). Returns the partial
        ``GenResult`` (``cancelled=True``), or None if unknown/finished."""
        now = time.perf_counter() if now is None else now
        key = (model, backend)
        q = self._queues[key]
        entry = self.reg.entry(*key)
        for r in q:
            if r.uid == uid:
                q.remove(r)
                entry.queued = max(0, entry.queued - 1)
                res = GenResult(uid=uid, prompt_len=len(r.tokens),
                                cancelled=True)
                res.latency = now - r.arrival_t
                self.stats.cancelled += 1
                self._note("cancel", model, now, uid=uid, where="queue")
                return self._absorb_retries(res)
        # cancel reaches DRAINING replicas too, not just placement —
        # a request riding out a drain is still the caller's to abort
        for eng in self.pool.service_engines(*key):
            res = eng.cancel(uid, now)
            if res is not None:
                entry.active_requests = max(0, entry.active_requests - 1)
                self.stats.cancelled += 1
                self._note("cancel", model, now, uid=uid, where="engine")
                return self._absorb_retries(res)
        return None

    # -- serve loop -----------------------------------------------------
    def dispatch(self, now: float) -> int:
        """Move queued requests into free engine slots (deadline-aware
        FIFO). Spins a replica from zero when demand requires it."""
        moved = 0
        for key, q in self._queues.items():
            if not q:
                continue
            model, backend = key
            entry = self.reg.entry(model, backend)
            # sweep expired requests FIRST: a queue of only-dead work
            # must not pay a spin-up it will never use
            if self.cfg.shed_expired:
                live = [r for r in q if not self._expire(key, r, now)]
                if len(live) != len(q):
                    q.clear()
                    q.extend(live)
                    entry.queued = len(q)
            if not q:
                continue
            if self.cfg.spin_on_demand and not self.pool.replicas(*key):
                self.pool.scale(model, backend, 1, now)
            # dispatch order: priority class first (high before low),
            # then cache-aware within a class — the biggest cached-prefix
            # reuse goes first (it skips most of its prefill, holding its
            # slot for the least time). Stable sort keeps FIFO fairness
            # between equal keys; only worth the radix walks when
            # something can actually dispatch.
            if len(q) > 1 and self.pool.free_slots(model, backend) > 0:
                prefix = (self.cfg.prefix_aware
                          and bool(self.pool.paged_replicas(*key)))
                if prefix or any(r.priority != q[0].priority for r in q):
                    ordered = sorted(q, key=lambda r: (
                        -r.priority,
                        -self.pool.prefix_peek(model, backend, r)
                        if prefix else 0))
                    q.clear()
                    q.extend(ordered)
            # retry backoff: requests still inside their not_before
            # window are held aside (and re-queued in order), never
            # dispatched early and never blocking the requests behind
            held: List[Request] = []
            while q and self.pool.free_slots(model, backend) > 0:
                req = q.popleft()
                if req.not_before > now:
                    held.append(req)
                    continue
                entry.queued = max(0, entry.queued - 1)
                self._to_engine(key, req, now)
                self.stats.dispatched += 1
                moved += 1
            for r in reversed(held):
                q.appendleft(r)
        return moved

    def _expire(self, key: _Key, req: Request, now: float) -> bool:
        if req.deadline_s is None or now - req.arrival_t <= req.deadline_s:
            return False
        res = GenResult(uid=req.uid, prompt_len=len(req.tokens),
                        timed_out=True)
        res.latency = now - req.arrival_t
        self._reaped.append((key, res))
        self.stats.expired += 1
        self._note("expire", key[0], now, uid=req.uid)
        if self._obs is not None and self._obs.flight is not None:
            self._obs.flight.note_expiry(now)
        return True

    def step(self, now: Optional[float] = None) -> List[Tuple[_Key, GenResult]]:
        """One serve-loop iteration over the whole pool: admit queued work,
        run ONE batched decode on every engine with work, reap finished."""
        with phase("scheduler.step"):
            now = time.perf_counter() if now is None else now
            self.stats.steps += 1
            with phase("scheduler.dispatch"):
                self.dispatch(now)
            out: List[Tuple[_Key, GenResult]]
            out, self._reaped = self._reaped, []
            out = [(k, self._absorb_retries(r)) for k, r in out]
            self._deltas = []
            flight = self._obs.flight if self._obs is not None else None
            for key, eng in list(self.pool.engines()):
                if not eng.has_work():
                    continue
                entry = self.reg.entry(*key)
                try:
                    results = eng.step()
                except Exception as exc:
                    # the flight ring holds the steps leading INTO the crash;
                    # dump before anything else happens to the replica
                    if flight is not None:
                        flight.note_exception(key[0], exc, now)
                    report = getattr(self.pool, "report_step_failure", None)
                    if not self.cfg.contain_failures or report is None:
                        raise
                    # containment: the circuit breaker degrades (replica keeps
                    # its state, retries next step) or quarantines (replica
                    # leaves placement; its in-flight work comes back as an
                    # evacuation list we resubmit deterministically). Results
                    # and deltas booked BEFORE a mid-step crash are salvaged —
                    # their device work completed, and retry dedup means
                    # nothing is ever emitted twice.
                    self._note("step_error", key[0], now, error=repr(exc))
                    evac = report(key[0], key[1], eng, exc, now)
                    results = eng.drain_finished()
                    self._deltas.extend(self._filter_deltas(eng.drain_deltas()))
                    if evac is not None:
                        self.stats.quarantines += 1
                        self._resubmit(key, evac, now)
                else:
                    ok = getattr(self.pool, "note_step_ok", None)
                    if ok is not None:
                        ok(eng, now)
                    self._deltas.extend(self._filter_deltas(eng.drain_deltas()))
                for res in results:
                    res = self._absorb_retries(res)
                    entry.active_requests = max(0, entry.active_requests - 1)
                    # stamp with the step's OWN clock: mixing perf_counter
                    # into a simulated `now` skewed the telemetry window
                    self.tel.record_latency(key[0], now, res.latency)
                    self.stats.completed += 1
                    if res.timed_out and flight is not None:
                        flight.note_expiry(now)
                    out.append((key, res))
            # draining replicas that emptied (or blew their deadline) retire
            # here; deadline evacuations are resubmitted like quarantines
            drains = getattr(self.pool, "finish_drains", None)
            if drains is not None:
                for dkey, evac in drains(now):
                    self._resubmit(dkey, evac, now)
            with phase("scheduler.gauges"):
                # paged-plane gauges: pool pressure / occupancy / prefix hit-rate
                # land in the same telemetry the Orchestrator ticks on, so Spin
                # can treat a block-starved service as a loaded one
                for model in {m for m, _ in self._queues}:
                    stats = self.pool.kv_stats(model)
                    if stats:
                        for name, value in stats.items():
                            self.tel.record_gauge(model, name, now, value)
                    # token-denominated load: queued prompt tokens + unfilled
                    # prefill backlog on the engines — the gauge that actually
                    # predicts time-to-first-token under chunked prefill
                    qtok = sum(self._queue_tokens(q)
                               for (m, _b), q in self._queues.items() if m == model)
                    self.tel.record_gauge(model, "queue_tokens", now, float(qtok))
                    self.tel.record_gauge(model, "backlog_tokens", now,
                                          float(qtok + self.pool.backlog_tokens(model)))
                    # resident KV bytes, labeled by occupancy state (composite
                    # label -> kv_pool_bytes{model=...,state=used|free} in the
                    # exposition)
                    if self._obs is not None:
                        # getattr: stub pools in tests duck-type ReplicaPool
                        kv_bytes = getattr(self.pool, "kv_bytes", None)
                        kb = kv_bytes(model) if kv_bytes is not None else None
                        if kb is not None:
                            used, free = kb
                            reg = self._obs.registry
                            reg.gauge("kv_pool_bytes",
                                      f"{model}|state=used").set(float(used), now)
                            reg.gauge("kv_pool_bytes",
                                      f"{model}|state=free").set(float(free), now)
            return out

    def drain_deltas(self) -> List[Tuple[int, int]]:
        """Fetch the latest step's (uid, token) streaming increments, in
        generation order per request."""
        out, self._deltas = self._deltas, []
        return out

    # -- fault containment ------------------------------------------------
    def _resubmit(self, key: _Key, evac, now: float) -> None:
        """Deterministic retry: every request evacuated off a failed (or
        drain-expired) replica goes back to the FRONT of its admission
        queue. Under ``retry_mode="replay"`` (default) the ORIGINAL
        request is resubmitted verbatim — same uid, same served prompt —
        so the substitute replica runs a bit-identical computation and
        regenerates the same tokens; deltas the caller already received
        are suppressed on the way out. Under ``"chain"`` the emitted
        tokens are chained onto the prompt and the per-request PRNG draw
        counter advanced past them (``prefix_draws``) — see
        SchedulerConfig for the exactness trade-off. Requests over the
        retry budget become structured FAILED results."""
        model, backend = key
        q = self._queues[key]
        entry = self.reg.entry(*key)
        replay = self.cfg.retry_mode != "chain"
        front: List[Request] = []
        for req, served, emitted in evac:
            entry.active_requests = max(0, entry.active_requests - 1)
            if served is None:
                # still queued inside the engine: requeue verbatim — an
                # evacuation is not a failed ATTEMPT for this request
                front.append(req)
                continue
            ctx = self._retry_ctx.get(req.uid)
            if replay:
                # a replay attempt regenerates from token 0, so the
                # delivered run is the LONGEST seen, not a concatenation
                prior = (ctx.prior if ctx is not None
                         and len(ctx.prior) >= len(emitted)
                         else list(emitted))
                prompt_len0 = (ctx.prompt_len0 if ctx is not None
                               else len(req.tokens))
            else:
                prior = (ctx.prior if ctx is not None else []) + list(emitted)
                prompt_len0 = (ctx.prompt_len0 if ctx is not None
                               else len(served))
            if req.retries >= self.cfg.max_retries:
                # budget exhausted: structured failure carrying every
                # token emitted so far (absorbed when the result flushes)
                self._retry_ctx[req.uid] = _RetryCtx(prior, req.retries,
                                                     prompt_len0)
                res = GenResult(uid=req.uid, prompt_len=len(req.tokens),
                                failed=True)
                res.latency = now - req.arrival_t
                self._reaped.append((key, res))
                self.stats.failed += 1
                self._note("retry_exhausted", model, now, uid=req.uid,
                           retries=req.retries)
                continue
            self._retry_ctx[req.uid] = _RetryCtx(
                prior, req.retries + 1, prompt_len0,
                to_skip=len(prior) if replay else 0)
            if replay:
                nreq = replace(
                    req, retries=req.retries + 1,
                    not_before=now + self.cfg.retry_backoff_s
                    * (req.retries + 1))
            else:
                nreq = replace(
                    req, tokens=list(served) + list(emitted),
                    prefix_draws=req.prefix_draws + len(emitted),
                    retries=req.retries + 1,
                    not_before=now + self.cfg.retry_backoff_s
                    * (req.retries + 1))
            front.append(nreq)
            self.stats.retries += 1
            if self._obs is not None:
                self._obs.registry.counter("retries_total", model).inc()
            self._note("retry", model, now, uid=req.uid,
                       emitted=len(emitted), retries=req.retries + 1)
        for r in reversed(front):
            q.appendleft(r)
        entry.queued += len(front)

    def _absorb_retries(self, res: GenResult) -> GenResult:
        """Fold retry history into a result leaving the scheduler. Replay
        mode: the final attempt regenerated the full token run, so the
        result is already whole unless it died early (budget exhaustion /
        cancel while queued), in which case the longest delivered run is
        restored. Chain mode: tokens emitted on earlier replicas rode in
        the retried prompt, so they are prepended here and ``prompt_len``
        is restored to the ORIGINAL served prompt."""
        ctx = self._retry_ctx.pop(res.uid, None)
        if ctx is None:
            return res
        if (self._obs is not None and not res.failed
                and not res.cancelled and not res.timed_out):
            # a retried request actually finishing = recovery succeeded
            self._obs.registry.counter("retries_recovered_total",
                                       "all").inc()
        if self.cfg.retry_mode != "chain":
            if len(res.new_tokens) < len(ctx.prior):
                res.new_tokens = list(ctx.prior)
        else:
            res.new_tokens = ctx.prior + res.new_tokens
            res.prompt_len = ctx.prompt_len0
            res.cached_tokens = min(res.cached_tokens, ctx.prompt_len0)
        res.retries = ctx.retries
        return res

    def _filter_deltas(self, deltas):
        """Drop stream deltas a replay retry re-generates for tokens the
        caller already received from the failed attempt."""
        if not self._retry_ctx:
            return deltas
        out = []
        for uid, tok in deltas:
            ctx = self._retry_ctx.get(uid)
            if ctx is not None and ctx.to_skip > 0:
                ctx.to_skip -= 1
                continue
            out.append((uid, tok))
        return out

    # -- internals -------------------------------------------------------
    def _to_engine(self, key: _Key, req: Request,
                   now: Optional[float] = None) -> None:
        if self._obs is not None:
            t = time.perf_counter() if now is None else now
            self._obs.registry.histogram(
                "sched_queue_wait_s",
                key[0]).observe(max(0.0, t - req.arrival_t))
        # cache-affine, token-aware, pack-first placement: prefer the
        # replica whose radix cache already holds this request's prefix
        # (its prefill mostly vanishes), then the one with the smallest
        # prefill backlog in TOKENS (two replicas with equal free slots
        # can differ 100x in pending prefill work under chunking), then
        # fill the busiest replica with a free slot. Densest batches
        # extract the most from iteration-level batching, and replicas
        # the pool may retire stay drained.
        cands = [g for g in self.pool.replicas(*key) if g.free_slots() > 0]
        eng = min(cands, key=lambda g: (
            -(g.prefix_peek(req) if g.paged else 0),
            g.pending_tokens(), g.free_slots()))
        eng.submit(req)
        self.reg.entry(*key).active_requests += 1
