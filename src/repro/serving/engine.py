"""In-process inference engine: continuous batching under a token budget.

Real execution (CPU here, TPU mesh in production): one global KV-cache
pool of ``max_batch`` slots. Every ``step()`` spends ONE token budget
across the whole batch — one decode token per active slot, committed
first, plus bounded CHUNKS of pending prefills with what remains. Long
prompts amortize over many steps instead of stalling every in-flight
decode behind a whole-prompt prefill (the head-of-line blocking the old
admit-then-decode split had), which is exactly the iteration-level
discipline vLLM/Sarathi-style chunked prefill uses.

The unified schedule per step:

  1. admission — queued requests claim free slots (state only: the paged
     engine leases its KV blocks here; no model compute);
  2. prefill   — mid-prefill slots advance their cursor by up to
     ``chunk_tokens``, oldest admission first, throttled by what the
     decode tokens left of ``step_token_budget``. A chunk attends the
     slot's cached KV plus itself (causal); the LAST chunk's logits
     sample the request's first token, and TTFT is stamped when that
     token reaches the host;
  3. decode    — one batched decode over every slot whose prefill is
     complete (including slots that finished in step 2: their first
     token joins this batch, matching the old admit-then-decode flow
     token for token).

The engine reports per-request TTFT / latency / completion, which is
exactly the telemetry the Pick-and-Spin control loop consumes.

Two cache disciplines share the slot/step/chunk machinery:
``InferenceEngine`` keeps the dense per-slot (max_batch, max_seq) cache
(chunks append through ``dense_gather_slot``/``dense_scatter_slot``),
while ``PagedInferenceEngine`` leases fixed-size KV blocks from a global
``kvpool.BlockPool`` with radix prefix reuse and copy-on-write sharing —
admission gated on free blocks, blocks freed the step a request
finishes, prefix hits skipping the cached part of prefill, and every
completed chunk's full blocks registered for reuse as soon as their KV
is valid.

Sampling uses a PER-REQUEST PRNG stream (engine seed x uid x token
index), so a request's sampled tokens never depend on which other
requests share its batch — serve it alone or under load, same tokens.

DEVICE-RESIDENT DECODE HOT PATH: the decode inner loop is ONE fused
jitted step — model decode plus per-row sampling (``sampling.sample_rows``,
greedy and stochastic unified under masks) over persistent device-side
state buffers (last tokens, positions, active mask, per-slot
``SamplingParams`` fields, PRNG uid-keys and draw counters, block tables
on the paged engine), updated by jitted index ops at admission /
activation / reap instead of host ``np`` staging arrays rebuilt and
re-uploaded every step. Only the sampled ``(max_batch,)`` int32 token
ids cross the host boundary per decode iteration — the ``(max_batch,
V)`` logits never leave the device. With ``decode_burst=K`` and no
prefill backlog pending, ``step()`` runs K decode iterations inside one
``lax.scan`` dispatch with on-device EOS/length retirement (deltas
flushed per burst; K bounds how stale a cancel or deadline can go), the
throughput path for ``run()``/offline serving. Burst and stepwise
decoding are token-for-token equivalent under greedy and fixed seeds.

DEVICE-SIDE TERMINATION (both modes): every decode entry point — fused
step, burst, the batched first-token sample, and the speculative verify
— computes the EOS / max_new / out-of-room finish decision ON DEVICE
(``_finish_bits``) and retires the row there; the host receives the
reason bits alongside the token ids and is a pure bookkeeping consumer
(``_consume_reason``), adding only the wall-clock deadline the device
cannot see.

SPECULATIVE DECODING (``spec=SpecDraft(...)``): a small resident draft
model shares the engine's device state — its own KV cache (paged: a
second small ``BlockPool``, slots leased/retired with the target's) —
and each decode step becomes draft-K + one multi-token target verify
(``lm_paged_verify``/``lm_dense_verify``, logits at every fed position).
Acceptance is an on-device prefix mask: at each fed position the target
samples its would-be token with the SAME per-request PRNG key plain
decode would use (``fold_in(key, draws + j)``), a drafted token is
accepted iff it equals that sample, and the emitted tokens are exactly
the target's samples — so spec output is token-for-token identical to
plain decode under greedy AND seeded stochastic sampling by
construction, for ANY draft (only speed varies with draft quality).
Only one ``(max_batch, K+1)`` int32 id matrix (+ reason bits) crosses
to host per verify; the transfer guard stays in force.
"""
from __future__ import annotations

import math
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import init_cache, model_decode, model_prefill
from repro.models.attention import (dense_gather_slot, dense_scatter_slot,
                                    paged_gather_ctx, paged_scatter)
from repro.models.transformer import (copy_paged_block, init_paged_cache,
                                      lm_chunk_prefill, lm_dense_verify,
                                      lm_paged_decode, lm_paged_verify,
                                      supports_chunked, supports_paged)
from repro.obs.trace import phase
from repro.serving.backend import BackendProfile
from repro.serving.kvpool import BlockPool, RadixPrefixCache
from repro.serving.sampling import SamplingParams, sample_rows


@dataclass
class Request:
    uid: int
    tokens: List[int]
    sampling: SamplingParams
    deadline_s: Optional[float] = None
    arrival_t: float = 0.0
    priority: int = 1                             # api.Priority class (int)
    src_embeds: Optional[np.ndarray] = None       # encdec stub input
    cancelled: bool = False                       # queue tombstone (cancel())
    # deterministic retry-from-prefix: a resubmitted request carries its
    # already-emitted tokens at the END of ``tokens`` and starts its
    # per-request PRNG stream at draw index ``prefix_draws`` — so token
    # N of the recovered run samples with the SAME folded key as token N
    # of the unfailed one. ``max_new_tokens`` stays the ORIGINAL total
    # (the device's draws>=max_new check is absolute).
    prefix_draws: int = 0
    retries: int = 0                              # containment resubmissions
    not_before: float = 0.0                       # retry backoff gate


@dataclass
class GenResult:
    uid: int
    prompt_len: int
    new_tokens: List[int] = field(default_factory=list)
    ttft: float = 0.0
    latency: float = 0.0
    completed: bool = False                       # finished within limits
    timed_out: bool = False
    cancelled: bool = False                       # caller aborted it
    shed: bool = False                            # evicted at admission
    cached_tokens: int = 0                        # prompt tokens from prefix cache
    prefill_chunks: int = 0                       # prefill passes the prompt took
    kv_bytes: int = 0                             # peak KV bytes held (at release)
    drafted_tokens: int = 0                       # spec: draft proposals verified
    accepted_tokens: int = 0                      # spec: drafted tokens committed
    failed: bool = False                          # retry budget exhausted
    retries: int = 0                              # containment resubmissions


@dataclass
class _Slot:
    req: Optional[Request] = None
    res: Optional[GenResult] = None
    pos: int = 0                 # tokens with valid KV (next write position)
    done: bool = True
    # chunked-prefill cursor
    prompt: List[int] = field(default_factory=list)
    filled: int = 0              # prompt tokens cached so far (prefix incl.)
    prefilling: bool = False
    order: int = 0               # admission sequence (FIFO chunk scheduling)
    idx: int = 0                 # batch row (device-state buffer index)
    spec_ok: bool = False        # draft cache co-residency secured


@dataclass
class _PagedSlot(_Slot):
    table: Optional[np.ndarray] = None            # (blocks_per_seq,) int32
    blocks: List[int] = field(default_factory=list)   # ids this req refs
    spec_blocks: List[int] = field(default_factory=list)  # draft-pool leases


def _insert_impl(cache, rcache, slot):
    def put(path, g, r):
        axis = 0 if any(getattr(k, "key", None) == "prefix" for k in path) else 1
        return jax.lax.dynamic_update_slice_in_dim(g, r.astype(g.dtype),
                                                   slot, axis=axis)
    return jax.tree_util.tree_map_with_path(put, cache, rcache)


# ---------------------------------------------------------------------------
# device-resident decode state
#
# One stacked buffer per per-slot quantity the fused decode step needs, so
# the hot loop never rebuilds host arrays: admission/activation/reap touch
# single rows through jitted index ops, and the step itself reads/advances
# everything on device. ``draws`` mirrors ``len(res.new_tokens)`` (the
# PRNG token index), so a row's key for its n-th token is
# fold_in(fold_in(fold_in(seed, uid), n)) — identical to the host-side
# per-request streams this replaces, and independent of batch composition.


def init_device_state(max_batch: int, blocks_per_seq: Optional[int] = None):
    state = {
        "tokens": jnp.zeros((max_batch, 1), jnp.int32),   # last sampled token
        "pos": jnp.zeros((max_batch,), jnp.int32),        # next KV write slot
        "active": jnp.zeros((max_batch,), jnp.bool_),     # decoding rows
        "temp": jnp.zeros((max_batch,), jnp.float32),     # SamplingParams...
        "top_k": jnp.zeros((max_batch,), jnp.int32),
        "top_p": jnp.ones((max_batch,), jnp.float32),
        "key": jnp.zeros((max_batch, 2), jnp.uint32),     # fold_in(seed, uid)
        "draws": jnp.zeros((max_batch,), jnp.int32),      # tokens sampled
        "eos": jnp.full((max_batch,), -1, jnp.int32),     # -1: no eos_id
        "max_new": jnp.zeros((max_batch,), jnp.int32),
    }
    if blocks_per_seq is not None:                        # paged engines
        state["tables"] = jnp.zeros((max_batch, blocks_per_seq), jnp.int32)
    return state


def _occupy_impl(state, slot, base_key, uid, temp, top_k, top_p, eos,
                 max_new, pos0, draws0):
    """Admission index-op: load one row's sampling fields + uid key.
    ``draws0`` resumes a retried request's PRNG stream mid-way: its
    next token samples at draw index ``draws0`` — the index the token
    would have had on the unfailed replica."""
    return dict(
        state,
        tokens=state["tokens"].at[slot].set(0),
        pos=state["pos"].at[slot].set(pos0),
        active=state["active"].at[slot].set(False),
        temp=state["temp"].at[slot].set(temp),
        top_k=state["top_k"].at[slot].set(top_k),
        top_p=state["top_p"].at[slot].set(top_p),
        key=state["key"].at[slot].set(jax.random.fold_in(base_key, uid)),
        draws=state["draws"].at[slot].set(draws0),
        eos=state["eos"].at[slot].set(eos),
        max_new=state["max_new"].at[slot].set(max_new))


def _deactivate_impl(state, slot):
    """Reap index-op: retire one row from the decode batch. The row's
    temperature is zeroed too — a stale temp > 0 on a vacated slot would
    defeat ``sample_rows``'s all-greedy argmax short-circuit for every
    later step until the row is reoccupied."""
    return dict(state, active=state["active"].at[slot].set(False),
                temp=state["temp"].at[slot].set(0.0))


def _first_tokens_impl(state, logits, idx, pos_vals, tables):
    """Batched first-token sampling for every slot whose prefill just
    completed: one fused dispatch samples all of them from their final-
    chunk logits and activates their rows (token, position, draw counter,
    and — paged — block table). ``idx`` entries equal to ``max_batch``
    are pow2-bucket pads: their gathers clip harmlessly and their
    scatters drop."""
    keys = jax.vmap(jax.random.fold_in)(state["key"][idx],
                                        state["draws"][idx])
    toks = sample_rows(logits, state["temp"][idx], state["top_k"][idx],
                       state["top_p"][idx], keys)
    new = dict(
        state,
        tokens=state["tokens"].at[idx, 0].set(toks, mode="drop"),
        pos=state["pos"].at[idx].set(pos_vals, mode="drop"),
        active=state["active"].at[idx].set(True, mode="drop"),
        draws=state["draws"].at[idx].set(state["draws"][idx] + 1,
                                         mode="drop"))
    if tables is not None:
        new["tables"] = state["tables"].at[idx].set(tables, mode="drop")
    return toks, new


def _advance_impl(state, logits):
    """Fused sample-in-step: draw every row's next token ON DEVICE from
    the decode logits (greedy/stochastic unified under masks, per-row
    keys folded from the uid streams) and advance the cursors of active
    rows. The logits are consumed here — they are never materialized on
    host."""
    with jax.named_scope("sample"):
        active = state["active"]
        keys = jax.vmap(jax.random.fold_in)(state["key"], state["draws"])
        nxt = sample_rows(logits, state["temp"], state["top_k"],
                          state["top_p"], keys)
        nxt = jnp.where(active, nxt, 0).astype(jnp.int32)
        state = dict(state,
                     tokens=nxt[:, None],
                     pos=jnp.where(active, state["pos"] + 1, state["pos"]),
                     draws=jnp.where(active, state["draws"] + 1,
                                     state["draws"]))
    return nxt, state


# finish-reason bit protocol (device -> host): the host never re-derives
# termination from token values; it consumes these bits verbatim.
FINISH_EOS = 1
FINISH_MAX_NEW = 2
FINISH_ROOM = 4


def _finish_bits(state, nxt, max_seq):
    """On-device termination decision after a token lands — EOS /
    max_new_tokens / out of cache room as a per-row int32 bitmask
    (0: keep decoding). Wall-clock deadlines are the one rule that
    stays host-side (the device has no clock). Applied identically by
    the fused step, the burst scan, the first-token sample and the
    speculative verify, so stepwise and burst serving share one
    termination source of truth."""
    hit_eos = (state["eos"] >= 0) & (nxt == state["eos"])
    full = state["draws"] >= state["max_new"]
    room = state["pos"] >= max_seq - 1
    bits = (jnp.where(hit_eos, FINISH_EOS, 0)
            | jnp.where(full, FINISH_MAX_NEW, 0)
            | jnp.where(room, FINISH_ROOM, 0))
    return jnp.where(state["active"], bits, 0).astype(jnp.int32)


def _retire_impl(state, nxt, max_seq):
    """On-device retirement: drop rows whose finish bits fired."""
    with jax.named_scope("sample"):
        bits = _finish_bits(state, nxt, max_seq)
        return dict(state, active=state["active"] & (bits == 0)), bits


@dataclass(frozen=True)
class CompiledFns:
    """Jitted step functions for one (config, backend, max_seq) service.

    Shareable across replicas: a second replica of a live service reuses
    the first replica's XLA executables, so only the first spin-up of a
    service ever pays compile — the dominant real cold-start cost. The
    replica pool caches these across scale-to-zero (its "code cache").

    ``prefill``/``insert`` are the whole-prompt path (families without a
    chunk-append layout, and ``chunk_tokens=None``); the ``*_slot`` trio
    is the chunk-append path over the dense per-slot cache, compiled only
    when the family supports it.

    The decode hot path is the fused trio: ``fused_step`` (decode +
    in-step sampling, one dispatch per token), ``fused_burst`` (K fused
    iterations under one ``lax.scan`` dispatch; K is a static argument)
    and ``first_tokens`` (batched first-token sampling for prefills
    completing this step). ``occupy``/``deactivate`` are the index ops
    that maintain the device-resident state between steps.
    ``trace_counts`` counts ACTUAL retraces of the fused functions — the
    regression guard that ``step()`` isn't silently recompiling per
    step.
    """
    prefill: object
    decode: object
    insert: object
    gather_slot: object = None
    chunk_prefill: object = None
    scatter_slot: object = None
    fused_step: object = None
    fused_burst: object = None
    first_tokens: object = None
    occupy: object = None
    deactivate: object = None
    trace_counts: object = None


def _fused_fns(step_fn, max_seq: int):
    """Build the fused decode fields of a CompiledFns/PagedCompiledFns
    from ONE per-engine step closure ``step_fn(params, cache, state) ->
    (nxt, cache, state)`` (decode + ``_advance_impl``): ``fused_step``
    jits it with ``_retire_impl`` appended (device-side termination for
    STEPWISE serving too — the host consumes the reason bits instead of
    replaying EOS/length checks), ``fused_burst`` scans it K times with
    the same retirement between iterations — a single source of truth,
    so burst and stepwise can never diverge. The state-maintenance index
    ops are shared too (the state pytree layout differs only by the
    paged ``tables`` leaf, which they pass through untouched)."""
    traces = {"fused_step": 0, "fused_burst": 0}

    def _fused(params, cache, state):
        traces["fused_step"] += 1
        nxt, cache, state = step_fn(params, cache, state)
        state, bits = _retire_impl(state, nxt, max_seq)
        return nxt, bits, cache, state

    def _burst(params, cache, state, k):
        traces["fused_burst"] += 1

        def body(carry, _):
            cache, state = carry
            was = state["active"]
            nxt, cache, state = step_fn(params, cache, state)
            state, bits = _retire_impl(state, nxt, max_seq)
            # -1 marks rows that were not decoding this iteration, so
            # the whole burst transfer stays int32 (ids + reason bits)
            return (cache, state), (jnp.where(was, nxt, -1), bits)

        (cache, state), (toks, bits) = jax.lax.scan(body, (cache, state),
                                                    None, length=k)
        return toks, bits, cache, state

    def _first(state, logits, idx, pos_vals, tables):
        with jax.named_scope("sample"):
            toks, state = _first_tokens_impl(state, logits, idx, pos_vals,
                                             tables)
            # device-side termination for first tokens too: an EOS
            # straight out of prefill (or max_new_tokens=1) retires the
            # row before it ever joins a decode batch. Non-idx active rows
            # re-check their last token — a no-op by invariant (they
            # survived their own step's bits or they would not be active).
            allbits = _finish_bits(state, state["tokens"][:, 0], max_seq)
            state = dict(state, active=state["active"] & (allbits == 0))
            return toks, allbits[idx], state

    return dict(
        fused_step=jax.jit(_fused, donate_argnums=(1, 2)),
        fused_burst=jax.jit(_burst, static_argnums=(3,),
                            donate_argnums=(1, 2)),
        first_tokens=jax.jit(_first, donate_argnums=(0,)),
        occupy=jax.jit(_occupy_impl, donate_argnums=(0,)),
        deactivate=jax.jit(_deactivate_impl, donate_argnums=(0,)),
        trace_counts=traces)


def compile_fns(cfg: ModelConfig, backend: BackendProfile,
                max_seq: int) -> CompiledFns:
    qc = backend.q_chunk

    def _prefill(params, batch):
        return model_prefill(params, cfg, batch, max_seq, q_chunk=qc)

    def _decode(params, token, cache, pos):
        return model_decode(params, cfg, token, cache, pos)

    def _step(params, cache, state):
        # inactive rows park their ignored write at max_seq-1, a position
        # no live request ever stores KV in (prompts are capped at
        # max_seq - max_new - 1 and decode finishes before writing it)
        safe = jnp.where(state["active"], state["pos"], max_seq - 1)
        logits, cache = model_decode(params, cfg, state["tokens"], cache,
                                     safe)
        nxt, state = _advance_impl(state, logits)
        return nxt, cache, state

    extra = _fused_fns(_step, max_seq)
    if supports_chunked(cfg):
        def _chunk(params, tokens, ctx_kv, start, s_real):
            return lm_chunk_prefill(params, cfg, tokens, ctx_kv, start, s_real)

        extra.update(
            gather_slot=jax.jit(dense_gather_slot),
            chunk_prefill=jax.jit(_chunk),
            scatter_slot=jax.jit(dense_scatter_slot, donate_argnums=(0,)))
    return CompiledFns(prefill=jax.jit(_prefill), decode=jax.jit(_decode),
                       insert=jax.jit(_insert_impl, donate_argnums=(0,)),
                       **extra)


@dataclass(frozen=True)
class PagedCompiledFns:
    """Jitted step functions of a paged-cache service (same sharing story
    as ``CompiledFns``: one compile per service, reused across replicas
    and across scale-to-zero).

    Prefill is three functions, and that split is the perf point of the
    paged plane: ``gather`` READS the request's context blocks out of
    the pool (output is O(context)), ``prefill`` runs the model over one
    uncached CHUNK only, and ``scatter`` writes the new KV into the
    request's blocks with the pool buffer DONATED — an in-place O(chunk)
    update. The dense engine's whole-prompt admission rewrites its whole
    (max_batch, max_seq) cache per insert; here the pool is never
    re-materialized.

    The ``fused_*``/``first_tokens``/``occupy``/``deactivate`` fields
    carry the same device-resident decode hot path as ``CompiledFns``
    (the state pytree additionally holds the per-row block tables)."""
    gather: object           # (cache, table_ctx) -> ctx_kv
    prefill: object          # (params, tokens, ctx_kv, start, s_real)
    scatter: object          # (cache, new_kv, table, start, s_real)
    decode: object           # (params, token, cache, tables, pos)
    copy: object             # (cache, src_block, dst_block) — COW
    fused_step: object = None
    fused_burst: object = None
    first_tokens: object = None
    occupy: object = None
    deactivate: object = None
    trace_counts: object = None


def compile_paged_fns(cfg: ModelConfig, backend: BackendProfile,
                      max_seq: int, block_size: int) -> PagedCompiledFns:
    def _prefill(params, tokens, ctx_kv, start, s_real):
        return lm_chunk_prefill(params, cfg, tokens, ctx_kv, start, s_real)

    def _decode(params, token, cache, tables, pos):
        return lm_paged_decode(params, cfg, token, cache, tables, pos)

    def _step(params, cache, state):
        # -1 marks inactive rows: their pool write is dropped entirely
        pos = jnp.where(state["active"], state["pos"], -1)
        logits, cache = lm_paged_decode(params, cfg, state["tokens"], cache,
                                        state["tables"], pos)
        nxt, state = _advance_impl(state, logits)
        return nxt, cache, state

    return PagedCompiledFns(
        gather=jax.jit(paged_gather_ctx),
        prefill=jax.jit(_prefill),
        scatter=jax.jit(paged_scatter, donate_argnums=(0,)),
        decode=jax.jit(_decode, donate_argnums=(2,)),
        copy=jax.jit(copy_paged_block, donate_argnums=(0,)),
        **_fused_fns(_step, max_seq))


# ---------------------------------------------------------------------------
# speculative decoding: resident draft model + on-device verify


@dataclass(frozen=True)
class SpecConfig:
    """Serve-plane speculative-decoding request: which registry arch
    drafts, and how many tokens per verify. Threaded ReplicaPool ->
    GatewayConfig -> ``launch/serve.py --spec-draft/--spec-k``; the pool
    resolves it into a ``SpecDraft`` (config + initialized params) per
    replica."""
    draft_arch: str
    k: int = 4


@dataclass
class SpecDraft:
    """Resolved draft model an engine co-residents with its target:
    the draft's config + params, the drafted-token count K, and (paged)
    an optional draft-pool size override — the KV-pressure knob tests
    use to force the co-residency refusal path."""
    cfg: ModelConfig
    params: object
    k: int = 4
    num_blocks: Optional[int] = None


@dataclass(frozen=True)
class SpecFns:
    """Jitted functions of one (target, draft, K) speculative pair.
    ``step`` is the whole hot path — draft-K (a ``lax.scan`` of small-
    model decodes) + one multi-token target verify + on-device accept/
    emit/retire — in ONE dispatch; the ``gather``/``prefill``/
    ``scatter`` trio runs the draft's whole-prompt prefill into its own
    cache at admission time; ``set_table`` (paged) loads one row of the
    device-resident draft block table."""
    step: object
    gather: object = None
    prefill: object = None
    scatter: object = None
    set_table: object = None
    trace_counts: object = None


def compile_spec_fns(cfg: ModelConfig, dcfg: ModelConfig, max_seq: int,
                     k: int, block_size: Optional[int] = None) -> SpecFns:
    """Compile the draft/verify pair (paged when ``block_size`` is set).

    THE ACCEPTANCE RULE (exactness by construction): the verify forward
    yields target logits at every fed position j (conditioned on the
    true prefix t0, d1..dj). At each position the target samples its
    would-be token ``s_j = sample_rows(logits_j, ..., fold_in(key,
    draws+j))`` — byte-identical to what plain decode would have drawn
    there, greedy or stochastic. Draft d_{j+1} is accepted iff it EQUALS
    s_j, and the emitted tokens are the s_j themselves up to (and
    including) the first non-match — so the output stream never depends
    on the draft at all; the draft only decides how many of the K+1
    computed tokens are committable per dispatch. For stochastic
    sampling this is the rejection rule specialized to proposal ==
    target-with-same-key: the draft samples its OWN logits with the SAME
    per-request keys, so a well-aligned draft agrees with high
    probability and an identity draft agrees always.

    The draft scan runs K+1 iterations: iteration j feeds token j of
    [t0, d1..dK] (writing its KV into the draft cache — iteration K
    exists so d_K's KV lands for the all-accepted case) and samples the
    next draft; the last sample is discarded.
    """
    traces = {"spec_step": 0}
    S = k + 1

    def _span_sample(state, logits):
        """Target samples at all K+1 fed positions with the per-request
        keys plain decode would use (one flattened sample_rows call)."""
        B, V = logits.shape[0], logits.shape[-1]
        di = state["draws"][:, None] + jnp.arange(S)[None, :]
        keys = jax.vmap(jax.vmap(jax.random.fold_in, in_axes=(None, 0)))(
            state["key"], di)
        flat = sample_rows(logits.reshape(B * S, V),
                           jnp.repeat(state["temp"], S),
                           jnp.repeat(state["top_k"], S),
                           jnp.repeat(state["top_p"], S),
                           keys.reshape(B * S, 2))
        return flat.reshape(B, S)

    def _accept_emit(state, s_tok, drafts):
        """On-device accept-prefix + emission mask + retirement.

        ``acc`` = length of the matching draft prefix; candidate j may
        emit if j <= acc AND no earlier emitted candidate finished the
        request (EOS / max_new / room — the same ``_finish_bits`` rules,
        evaluated per candidate position). Returns the (B, S) id matrix
        (-1 past the emitted prefix) and the (B,) reason bits — the only
        buffers that cross to host."""
        active = state["active"]
        offs = jnp.arange(S)[None, :]
        match = (drafts == s_tok[:, :k]).astype(jnp.int32)
        acc = jnp.cumprod(match, axis=1).sum(axis=1)
        allowed = offs <= acc[:, None]
        hit_eos = ((state["eos"][:, None] >= 0)
                   & (s_tok == state["eos"][:, None]))
        full = state["draws"][:, None] + offs + 1 >= state["max_new"][:, None]
        room = state["pos"][:, None] + offs + 1 >= max_seq - 1
        bits = (jnp.where(hit_eos, FINISH_EOS, 0)
                | jnp.where(full, FINISH_MAX_NEW, 0)
                | jnp.where(room, FINISH_ROOM, 0)).astype(jnp.int32)
        stop = (allowed & (bits != 0)).astype(jnp.int32)
        prior = jnp.cumsum(stop, axis=1) - stop       # stops before j
        emit = active[:, None] & allowed & (prior == 0)
        n_emit = emit.sum(axis=1).astype(jnp.int32)   # >= 1 on active rows
        out = jnp.where(emit, s_tok, -1).astype(jnp.int32)
        last = jnp.clip(n_emit - 1, 0, k)[:, None]
        reason = jnp.where(
            active & (jnp.take_along_axis(stop, last, 1)[:, 0] != 0),
            jnp.take_along_axis(bits, last, 1)[:, 0], 0).astype(jnp.int32)
        last_tok = jnp.take_along_axis(out, last, 1)[:, 0]
        state = dict(
            state,
            tokens=jnp.where(active, last_tok,
                             state["tokens"][:, 0])[:, None].astype(jnp.int32),
            pos=jnp.where(active, state["pos"] + n_emit, state["pos"]),
            draws=jnp.where(active, state["draws"] + n_emit, state["draws"]),
            active=active & (reason == 0))
        return out, reason, state

    def _draft_next(state, active, logits, j):
        """Draft's proposal for global draw index draws+j: its own
        logits sampled under the target's key/params for that draw."""
        keys = jax.vmap(jax.random.fold_in)(state["key"],
                                            state["draws"] + j)
        nt = sample_rows(logits, state["temp"], state["top_k"],
                         state["top_p"], keys)
        return jnp.where(active, nt, 0).astype(jnp.int32)

    if block_size is not None:
        def _step(params, dparams, cache, dcache, state, dtables):
            traces["spec_step"] += 1
            active = state["active"]
            pos = jnp.where(active, state["pos"], -1)
            # last position a row may legitimately write: the fed span
            # can overrun a short request's leased blocks (zero-padded
            # tables alias block 0 — another request's KV); emission
            # stops at max_new before any capped-out position matters
            cap = state["pos"] + (state["max_new"] - state["draws"])

            def dbody(carry, j):
                dc, tok, dp = carry
                dpw = jnp.where(active & (dp <= cap), dp, -1)
                logits, dc = lm_paged_decode(dparams, dcfg, tok, dc,
                                             dtables, dpw)
                nt = _draft_next(state, active, logits, j)
                return (dc, nt[:, None], jnp.where(active, dp + 1, dp)), nt

            (dcache, _, _), dseq = jax.lax.scan(
                dbody, (dcache, state["tokens"], pos), jnp.arange(S))
            drafts = dseq[:k].swapaxes(0, 1)          # (B, K): d_1..d_K
            fed = jnp.concatenate([state["tokens"], drafts], axis=1)
            logits, cache = lm_paged_verify(params, cfg, fed, cache,
                                            state["tables"], pos, cap)
            s_tok = _span_sample(state, logits)
            out, reason, state = _accept_emit(state, s_tok, drafts)
            return out, reason, cache, dcache, state

        def _dprefill(dparams, tokens, ctx_kv, start, s_real):
            return lm_chunk_prefill(dparams, dcfg, tokens, ctx_kv, start,
                                    s_real)

        return SpecFns(
            step=jax.jit(_step, donate_argnums=(2, 3, 4)),
            gather=jax.jit(paged_gather_ctx),
            prefill=jax.jit(_dprefill),
            scatter=jax.jit(paged_scatter, donate_argnums=(0,)),
            set_table=jax.jit(lambda tabs, i, row: tabs.at[i].set(row),
                              donate_argnums=(0,)),
            trace_counts=traces)

    def _step(params, dparams, cache, dcache, state):
        traces["spec_step"] += 1
        active = state["active"]

        def dbody(carry, j):
            dc, tok, dp = carry
            safe = jnp.where(active, dp, max_seq - 1)
            logits, dc = model_decode(dparams, dcfg, tok, dc, safe)
            nt = _draft_next(state, active, logits, j)
            return (dc, nt[:, None], jnp.where(active, dp + 1, dp)), nt

        (dcache, _, _), dseq = jax.lax.scan(
            dbody, (dcache, state["tokens"], state["pos"]), jnp.arange(S))
        drafts = dseq[:k].swapaxes(0, 1)
        fed = jnp.concatenate([state["tokens"], drafts], axis=1)
        pos = jnp.where(active, state["pos"], -1)
        logits, cache = lm_dense_verify(params, cfg, fed, cache, pos)
        s_tok = _span_sample(state, logits)
        out, reason, state = _accept_emit(state, s_tok, drafts)
        return out, reason, cache, dcache, state

    def _dprefill(dparams, tokens, ctx_kv, start, s_real):
        return lm_chunk_prefill(dparams, dcfg, tokens, ctx_kv, start, s_real)

    return SpecFns(
        step=jax.jit(_step, donate_argnums=(2, 3, 4)),
        gather=jax.jit(dense_gather_slot),
        prefill=jax.jit(_dprefill),
        scatter=jax.jit(dense_scatter_slot, donate_argnums=(0,)),
        trace_counts=traces)


class InferenceEngine:
    """Continuous-batching engine for one (model x backend) instance.

    ``chunk_tokens`` bounds how many prompt tokens one prefill pass may
    cover (None: whole prompt in one pass). ``step_token_budget`` caps
    the tokens one ``step()`` spends across decode + prefill (None:
    unbounded — decode everything, prefill everything admitted).
    ``decode_burst=K`` (opt-in, default 1) lets a step with NO prefill
    backlog run K fused decode iterations in one device dispatch.
    """

    paged = False

    def __init__(self, cfg: ModelConfig, params, backend: BackendProfile,
                 max_seq: int = 512, seed: int = 0, fns=None,
                 chunk_tokens: Optional[int] = None,
                 step_token_budget: Optional[int] = None,
                 decode_burst: int = 1, obs=None,
                 spec: Optional[SpecDraft] = None, fault=None):
        self.cfg = cfg
        self.params = params
        self.backend = backend
        self.max_seq = max_seq
        self.max_batch = backend.max_batch
        # fault injection (repro.serving.faults.FaultInjector): the
        # seeded chaos hook at the top of step(). None (the default) is
        # one attribute test per step. ``poisoned`` flips when a step
        # dies MID-flight — host/device bookkeeping may have diverged,
        # so containment must quarantine rather than re-place on it.
        self._fault = fault
        self.poisoned = False
        # observability (repro.obs.EngineObs): shared metrics registry +
        # request tracer + this engine's service labels. None (the
        # default for standalone engines) keeps every hook a single
        # attribute test — and every hook is HOST-side bookkeeping on
        # values the step already pulled, never a new device sync.
        self._obs = obs
        # 0 means "whole prompt" (the launcher's CLI convention); a raw 0
        # reaching the chunk sizing would stall the cursor forever
        self.chunk_tokens = max(1, chunk_tokens) if chunk_tokens else None
        self.step_token_budget = (max(1, step_token_budget)
                                  if step_token_budget else None)
        self.decode_burst = max(1, decode_burst)
        self._base_key = jax.random.PRNGKey(seed)
        self._slots = [self._make_slot() for _ in range(self.max_batch)]
        for i, s in enumerate(self._slots):
            s.idx = i
        self._queue: Deque[Request] = deque()
        self._queue_tomb = 0                   # cancelled-in-queue count
        # O(1) cancel index: uid -> queued Request, or the _Slot serving it
        self._by_uid: Dict[int, object] = {}
        self._order = 0
        self._kv_dtype = jnp.bfloat16 if backend.kv_dtype == "bfloat16" else jnp.float32
        self.cache = self._init_cache()
        # resident KV bytes from the pool tensors' own shape metadata —
        # int8 quantized pools (k/v int8 + f32 scales) land at their true
        # width. Shape inspection only: no device sync.
        self._cache_bytes = int(sum(
            x.nbytes for x in jax.tree_util.tree_leaves(self.cache)))
        self._register_cache_bytes()
        self._dstate = self._init_dstate()
        self._finished: List[GenResult] = []
        # (uid, token) streaming deltas of the CURRENT step — cleared at
        # the top of each step(), so a caller draining between steps sees
        # exactly one decode iteration's worth of tokens (one BURST's
        # worth under decode_burst)
        self._deltas: List[Tuple[int, int]] = []
        # slots whose prefill completes this step, awaiting the batched
        # first-token sample: (slot, final-chunk logits) pairs
        self._pending_first: List[Tuple["_Slot", object]] = []
        self.fns = fns or self._compile()
        self._bind_fns()
        # speculative decoding: a viable draft co-residents its own KV
        # cache beside the target's; an unviable one degrades to plain
        # fused stepwise (self.spec stays None — no other path changes)
        self.spec: Optional[SpecDraft] = None
        self._spec_bytes = 0
        self._spec_drafted = 0            # lifetime drafted/accepted (gauge)
        self._spec_accepted = 0
        self._spec_win = [0, 0]           # draft-collapse detection window
        if spec is not None and self._spec_viable(spec):
            self.spec = spec
            self._init_spec()

    # hooks a paged subclass overrides ------------------------------------
    def _make_slot(self) -> "_Slot":
        return _Slot()

    def _init_cache(self):
        return init_cache(self.cfg, self.max_batch, self.max_seq,
                          self._kv_dtype)

    def _init_dstate(self):
        return init_device_state(self.max_batch)

    def _compile(self):
        return compile_fns(self.cfg, self.backend, self.max_seq)

    def _bind_fns(self) -> None:
        self._prefill = self.fns.prefill
        self._decode = self.fns.decode
        self._insert = self.fns.insert
        self._gather_slot = self.fns.gather_slot
        self._chunk_prefill = self.fns.chunk_prefill
        self._scatter_slot = self.fns.scatter_slot
        self._bind_fused()

    def _bind_fused(self) -> None:
        self._fused_step = self.fns.fused_step
        self._fused_burst = self.fns.fused_burst
        self._first_fn = self.fns.first_tokens
        self._occupy_fn = self.fns.occupy
        self._deactivate_fn = self.fns.deactivate

    def _chunkable(self) -> bool:
        """Chunk-append available AND requested for this engine."""
        return self.chunk_tokens is not None and self.fns.chunk_prefill is not None

    # -- speculative decoding hooks ---------------------------------------
    def _spec_viable(self, spec: SpecDraft) -> bool:
        """Can this draft co-reside? Vocab must match (acceptance compares
        token ids) and both models need the multi-token chunk/verify
        trunk. Failing the gate is graceful: plain fused stepwise."""
        return (spec.cfg.vocab_size == self.cfg.vocab_size
                and supports_chunked(spec.cfg)
                and supports_chunked(self.cfg))

    def _build_spec_cache(self, spec: SpecDraft):
        """Draft KV storage (dense: its own per-slot cache)."""
        return init_cache(spec.cfg, self.max_batch, self.max_seq,
                          self._kv_dtype)

    def _init_spec(self) -> None:
        """Allocate the draft's device residency and compile the pair.
        KV-pressure gate: if the draft cache would outweigh the target's
        own, the draft cannot co-reside — drop to plain decode rather
        than let the helper starve the helped."""
        spec = self.spec
        dcache = self._build_spec_cache(spec)
        nbytes = int(sum(
            x.nbytes for x in jax.tree_util.tree_leaves(dcache)))
        if dcache is None or nbytes > self._cache_bytes:
            self.spec = None
            return
        self._spec_cache = dcache
        self._spec_bytes = nbytes
        self.sfns = self._compile_spec(spec)
        self._sgather = self.sfns.gather
        self._sprefill = self.sfns.prefill
        self._sscatter = self.sfns.scatter

    def _compile_spec(self, spec: SpecDraft) -> SpecFns:
        return compile_spec_fns(self.cfg, spec.cfg, self.max_seq, spec.k)

    def _spec_dispatch(self):
        """One fused draft-K + verify dispatch over the engine's device
        state. Returns (out ids, reason bits, cache, dcache, state)."""
        return self.sfns.step(self.params, self.spec.params, self.cache,
                              self._spec_cache, self._dstate)

    def _spec_ready(self, active: List[int]) -> bool:
        """Spec runs only when EVERY active row has draft residency —
        a row without a draft-cache lease would read/clobber another
        row's draft KV. Mixed batches fall back to plain stepwise."""
        return (self.spec is not None
                and all(self._slots[i].spec_ok for i in active))

    def _spec_prefill_slot(self, slot: "_Slot") -> None:
        """Whole-prompt draft prefill at admission-completion time: the
        draft needs KV for the ENTIRE prompt (including any part the
        target skipped via prefix cache — the draft pool has no radix),
        in one bucketed pass into its own cache."""
        n = slot.filled
        sb = self._bucket_up(n)
        padded = np.zeros((1, sb), np.int32)
        padded[0, :n] = slot.prompt[:n]
        ctx = self._sgather(self._spec_cache, jnp.int32(slot.idx))
        _, new_kv = self._sprefill(self.spec.params, jnp.asarray(padded),
                                   ctx, jnp.int32(0), jnp.int32(n))
        self._spec_cache = self._sscatter(self._spec_cache, new_kv,
                                          jnp.int32(slot.idx), jnp.int32(0),
                                          jnp.int32(n))

    def _register_cache_bytes(self) -> None:
        """Hook: publish cache geometry (paged sets bytes_per_block)."""

    def _slot_kv_bytes(self, slot: "_Slot") -> int:
        """KV bytes a slot holds at release — dense: its fixed share of
        the pre-allocated (max_batch, max_seq) cache."""
        return self._cache_bytes // self.max_batch

    def _release(self, slot: "_Slot", register_prefix: bool = True) -> None:
        """Reap hook: account the request's peak KV footprint, then free
        per-request cache resources (nothing to free on dense). Paged
        overrides MUST call super() before dropping block leases."""
        if slot.res is not None:
            b = self._slot_kv_bytes(slot)
            slot.res.kv_bytes = b
            if self._obs is not None:
                from repro.obs.cost import KV_BYTE_BUCKETS
                self._obs.registry.histogram(
                    "kv_bytes_per_request", self._obs.model,
                    bounds=KV_BYTE_BUCKETS).observe(float(b))

    # -- resident-memory accounting --------------------------------------
    def resident_bytes(self) -> int:
        """HBM this replica pins: params (config param count x dtype
        width) + the KV cache/pool tensors — and, under speculative
        decoding, the resident draft's params + its own KV cache."""
        from repro.obs.cost import param_bytes
        total = param_bytes(self.cfg) + self._cache_bytes
        if self.spec is not None:
            total += param_bytes(self.spec.cfg) + self._spec_bytes
        return total

    def kv_pool_bytes(self) -> Tuple[int, int]:
        """(used, free) KV bytes — dense: occupied-slot shares of the
        pre-allocated cache."""
        share = self._cache_bytes // self.max_batch
        busy = sum(1 for s in self._slots if not s.done)
        return busy * share, (self.max_batch - busy) * share

    # -- public API ----------------------------------------------------------
    def submit(self, req: Request) -> None:
        req.arrival_t = req.arrival_t or time.perf_counter()
        self._queue.append(req)
        self._by_uid[req.uid] = req

    def cancel(self, uid: int, now: Optional[float] = None
               ) -> Optional[GenResult]:
        """Abort a request wherever it is, O(1) at any occupancy via the
        uid index. Queued: tombstoned (skipped at admission) before ever
        touching a slot. In a slot (mid-prefill or mid-decode): the slot
        is freed immediately and — on the paged engine — its KV blocks go
        back to the pool without registering in the prefix cache (the
        caller abandoned the work). Returns the partial ``GenResult``
        (``cancelled=True``), or None if ``uid`` is unknown/already
        finished here."""
        now = time.perf_counter() if now is None else now
        obj = self._by_uid.pop(uid, None)
        if obj is None:
            return None
        if isinstance(obj, Request):          # still queued: tombstone
            obj.cancelled = True
            self._queue_tomb += 1
            while self._queue and self._queue[0].cancelled:
                self._queue.popleft()         # amortized front sweep
                self._queue_tomb -= 1
            res = GenResult(uid=uid, prompt_len=len(obj.tokens),
                            cancelled=True)
            res.latency = now - obj.arrival_t
            return res
        slot = obj
        res = slot.res
        res.latency = now - slot.req.arrival_t
        res.cancelled = True
        res.completed = False
        self._release(slot, register_prefix=False)
        self._clear_slot(slot)
        slot.res = None
        return res

    def evacuate(self) -> List[Tuple[Request, Optional[List[int]],
                                     List[int]]]:
        """Containment dump: hand back every live request this engine
        holds so a healthy replica can take them over. Queued requests
        come back untouched ``(request, None, [])``; in-slot ones as
        ``(request, served prompt, emitted tokens)`` — the served prompt
        is the post-cap/post-bucket token list actually prefilled, which
        is what a deterministic retry must chain onto. All slot
        resources are released WITHOUT registering prefixes (this
        replica's cache dies with it); the engine is empty afterwards."""
        out: List[Tuple[Request, Optional[List[int]], List[int]]] = []
        for r in self._queue:
            self._by_uid.pop(r.uid, None)
            if not r.cancelled:
                out.append((r, None, []))
        self._queue.clear()
        self._queue_tomb = 0
        for s in self._slots:
            if s.done or s.req is None:
                continue
            emitted = list(s.res.new_tokens) if s.res is not None else []
            out.append((s.req, list(s.prompt), emitted))
            self._release(s, register_prefix=False)
            self._clear_slot(s)
            s.res = None
        self._pending_first = []
        return out

    def drain_deltas(self) -> List[Tuple[int, int]]:
        """Fetch-and-clear the current step's (uid, token) stream deltas."""
        out, self._deltas = self._deltas, []
        return out

    def _queued(self) -> int:
        """Live (non-tombstoned) queued requests."""
        return len(self._queue) - self._queue_tomb

    def has_work(self) -> bool:
        return self._queued() > 0 or any(not s.done for s in self._slots)

    def idle_slots(self) -> int:
        """Raw free decode slots (no queue/capacity accounting)."""
        return sum(1 for s in self._slots if s.done)

    def free_slots(self) -> int:
        """Slots a scheduler may still fill (free minus already queued),
        clamped at 0: the internal queue can exceed the free slots, and a
        negative count would corrupt scheduler admission math."""
        return max(0, self.idle_slots() - self._queued())

    def pending_tokens(self) -> int:
        """Prefill backlog in TOKENS: queued prompt tokens plus the
        unfilled remainder of every mid-prefill slot. The scheduler's
        token-budget load gauge — two replicas with equal free slots can
        hide a 100x difference here."""
        # queued prompts count at their SERVED size (admission keeps only
        # the last max_seq - max_new - 1 tokens; raw oversized prompts
        # would report phantom load)
        queued = sum(
            min(len(r.tokens),
                max(self.max_seq - self._decode_budget(r) - 1, 1))
            for r in self._queue if not r.cancelled)
        inflight = sum(len(s.prompt) - s.filled for s in self._slots
                       if not s.done and s.prefilling)
        return queued + inflight

    def step(self) -> List[GenResult]:
        """One token-budget iteration: admit, prefill chunks, decode.

        Fault-injection hook first (BEFORE any device work, so an
        injected crash is clean: state is exactly as the previous step
        left it), then the real step with a poison latch — any
        mid-flight exception marks the engine unrecoverable for the
        containment layer."""
        if self._fault is not None:
            fired = self._fault.begin_step()
            if fired and self._obs is not None:
                for kind in fired:
                    self._obs.registry.counter(
                        "fault_injected_total",
                        f"{self._obs.model}|kind={kind}").inc()
            if "step_error" in fired:
                from repro.serving.faults import InjectedFault
                raise InjectedFault(
                    f"injected step_error at step {self._fault.step_no}")
        try:
            with phase("engine.step"):
                return self._step_inner()
        except BaseException:
            self.poisoned = True
            raise

    def _step_inner(self) -> List[GenResult]:
        t0 = time.perf_counter() if self._obs is not None else 0.0
        self._deltas = []                 # this step's streaming increments
        self._pending_first = []
        # 1) admission (a paged engine may refuse — out of KV blocks — in
        #    which case the request stays queued for a later step).
        #    Tombstoned (cancelled-in-queue) entries drain here for free.
        deny_kv = self._fault is not None and self._fault.deny_kv
        with phase("engine.admit"):
            for slot in self._slots:
                while self._queue and self._queue[0].cancelled:
                    self._queue.popleft()
                    self._queue_tomb -= 1
                if not self._queue:
                    break
                if deny_kv:    # injected allocation failure: stay queued
                    break
                if slot.done:
                    if not self._begin(slot.idx, self._queue[0]):
                        break
                    self._queue.popleft()
        # requests sharing this step's batch — the cost ledger splits the
        # step's wall duration evenly across them (host-side list of ids
        # already in slot state; no device traffic)
        step_uids = ([s.req.uid for s in self._slots if not s.done]
                     if self._obs is not None and self._obs.meter is not None
                     else None)
        # 2) budget: decode tokens are committed first — in-flight decodes
        #    must never stall behind prefill (that's the whole point);
        #    the remainder throttles prefill chunks. Slots whose LAST
        #    chunk completes below join this step's decode uncharged
        #    (bounded by max_batch; the overdraft buys them the same
        #    admit-then-decode cadence the old engine had).
        decoding = sum(1 for s in self._slots
                       if not s.done and not s.prefilling)
        rem = (None if self.step_token_budget is None
               else max(self.step_token_budget - decoding, 0))
        # 3) prefill chunks, oldest admission first
        for i in sorted((i for i, s in enumerate(self._slots)
                         if not s.done and s.prefilling),
                        key=lambda i: self._slots[i].order):
            if rem is not None and rem <= 0:
                break
            with phase("engine.prefill"):       # one chunk
                rem = self._prefill_step(i, self._slots[i], rem)
        # 3b) ONE batched dispatch samples the first token of every slot
        #     whose last chunk just ran; they join this step's decode
        with phase("engine.first_tokens"):
            self._finish_first_tokens()
        # 4) decode all fully-prefilled slots: one fused device step per
        #    token, or a K-iteration burst when nothing is waiting to
        #    prefill (the offline/throughput path)
        active = [i for i, s in enumerate(self._slots)
                  if not s.done and not s.prefilling]
        if active:
            with phase("engine.decode"):
                if self._spec_ready(active):
                    self._decode_spec(active)
                elif (self.decode_burst > 1 and self._queued() == 0
                        and not any(s.prefilling for s in self._slots
                                    if not s.done)):
                    self._decode_burst(active)
                else:
                    self._decode_once(active)
        if self._obs is not None:
            with phase("engine.record"):
                self._record_step(t0, step_uids, rem)
        return self.drain_finished()

    def _record_step(self, t0: float, step_uids=None, rem=None) -> None:
        """Per-step host-side metrics: step wall time, tokens emitted
        (decode + first tokens, i.e. this step's delta count), and the
        fused-fn retrace total surfaced as a gauge (a climbing value
        under steady traffic is the silent-recompile regression the
        PR-5 trace-count guard tests for).  Also feeds the chip-second
        ledger (wall interval split across ``step_uids``) and the flight
        recorder's snapshot ring — both pure host-side appends."""
        reg, m = self._obs.registry, self._obs.model
        t1 = time.perf_counter()
        reg.histogram("engine_step_s", m).observe(t1 - t0)
        ntok = len(self._deltas)
        reg.histogram("engine_tokens_per_step", m).observe(float(ntok))
        if ntok:
            reg.counter("engine_tokens", m).inc(ntok)
        if self.fns.trace_counts:
            reg.gauge("engine_retraces", m).set(
                float(sum(self.fns.trace_counts.values())))
        meter = self._obs.meter
        if meter is not None:
            self._obs.cost.on_step(meter, t0, t1, step_uids or ())
        fl = self._obs.flight
        if fl is not None:
            spent = (self.step_token_budget - rem
                     if self.step_token_budget is not None and rem is not None
                     else ntok)
            snap = dict(
                active=sum(1 for s in self._slots if not s.done),
                pending_tokens=self.pending_tokens(),
                free_blocks=getattr(getattr(self, "pool", None),
                                    "num_free", -1),
                tokens=ntok, budget_spent=spent, burst=self.decode_burst)
            if self.spec is not None:
                # draft-collapse forensics ride the snapshot ring: the
                # accept rate at every step leading up to an anomaly dump
                snap["spec_accept_rate"] = (
                    self._spec_accepted / self._spec_drafted
                    if self._spec_drafted else -1.0)
            fl.record_step(m, t1, **snap)

    # -- fused decode (device-resident hot path) --------------------------
    def _decode_once(self, active: List[int]) -> None:
        """One fused decode+sample dispatch; the ONLY device->host
        traffic is the (max_batch,) int32 token-id vector plus the
        (max_batch,) int32 finish-reason bits — termination is decided
        on device, the host just books the result."""
        nxt, bits, self.cache, self._dstate = self._fused_step(
            self.params, self.cache, self._dstate)
        with phase("engine.sync"):
            # servelint: disable=SL002 -- the designed per-step sync point
            toks, bits = jax.device_get((nxt, bits))
        t = time.perf_counter()
        tracer = self._obs.tracer if self._obs is not None else None
        for i in active:
            s = self._slots[i]
            tok = int(toks[i])
            uid = s.req.uid
            s.res.new_tokens.append(tok)
            self._deltas.append((uid, tok))
            s.pos += 1
            if tracer is not None:
                tracer.on_tokens(uid, t)
            self._consume_reason(s, t, int(bits[i]))

    def _decode_burst(self, active: List[int]) -> None:
        """K fused decode iterations inside one ``lax.scan`` dispatch,
        with on-device EOS/length retirement; the host replays the
        (K, max_batch) token ids (-1: row not decoding that iteration)
        and consumes the matching reason bits. Wall-clock deadlines
        resolve only at the burst boundary — K bounds that staleness,
        which is why the burst stays opt-in and bounded rather than
        running to EOS."""
        k = self.decode_burst
        toks, bits, self.cache, self._dstate = self._fused_burst(
            self.params, self.cache, self._dstate, k)
        with phase("engine.sync"):
            # servelint: disable=SL002 -- the designed per-burst sync point
            toks, bits = jax.device_get((toks, bits))
        counts: Dict[int, int] = {}
        for j in range(k):
            t = time.perf_counter()
            for i in active:
                s = self._slots[i]
                # s.done: the host finished this row at an earlier burst
                # iteration (e.g. a lapsed deadline the device couldn't
                # see) — any tokens the device over-ran are dropped
                if s.done or toks[j, i] < 0:
                    continue
                tok = int(toks[j, i])
                uid = s.req.uid
                s.res.new_tokens.append(tok)
                self._deltas.append((uid, tok))
                s.pos += 1
                counts[uid] = counts.get(uid, 0) + 1
                self._consume_reason(s, t, int(bits[j, i]))
        if self._obs is not None:
            # one tracer call per request per burst: the replay wall
            # since the request's previous token spreads evenly over its
            # K accepted tokens (per-iteration replay stamps would report
            # ~0 ITL for every token after the first)
            t = time.perf_counter()
            tracer = self._obs.tracer
            for uid, n in counts.items():
                tracer.on_tokens(uid, t, n)
            self._obs.registry.gauge("engine_burst_depth",
                                     self._obs.model).set(float(k))

    def _decode_spec(self, active: List[int]) -> None:
        """One speculative draft-K + verify dispatch: up to K+1 tokens
        per active row for ONE target forward. The only device->host
        traffic is the (max_batch, K+1) int32 id matrix (-1 past each
        row's emitted prefix) and the (max_batch,) reason bits — the
        draft's logits, the verify logits and the acceptance mask all
        stay on device."""
        out, reason, self.cache, self._spec_cache, self._dstate = \
            self._spec_dispatch()
        with phase("engine.sync"):
            # servelint: disable=SL002 -- the designed per-verify sync point
            out, reason = jax.device_get((out, reason))
        t = time.perf_counter()
        k = self.spec.k
        counts: Dict[int, int] = {}
        drafted = accepted = 0
        for i in active:
            s = self._slots[i]
            uid = s.req.uid
            n = 0
            for tok in out[i]:             # emitted prefix, then -1 pads
                if tok < 0:
                    break
                tok = int(tok)
                s.res.new_tokens.append(tok)
                self._deltas.append((uid, tok))
                s.pos += 1
                n += 1
            counts[uid] = n
            s.res.drafted_tokens += k
            s.res.accepted_tokens += max(n - 1, 0)
            drafted += k
            accepted += max(n - 1, 0)
            self._consume_reason(s, t, int(reason[i]))
        self._spec_drafted += drafted
        self._spec_accepted += accepted
        # draft-collapse watch: a draft that stops agreeing makes every
        # verify pay K+1 positions for ~1 token — flag it for the flight
        # recorder once enough evidence accumulates
        self._spec_win[0] += drafted
        self._spec_win[1] += accepted
        if self._obs is not None:
            reg, m = self._obs.registry, self._obs.model
            tracer = self._obs.tracer
            hist = reg.histogram("spec_accept_len", m,
                                 bounds=(0.0, 1.0, 2.0, 4.0, 8.0, 16.0))
            for uid, n in counts.items():
                if n:
                    tracer.on_tokens(uid, t, n)
                hist.observe(float(max(n - 1, 0)))
            if self._spec_drafted:
                reg.gauge("spec_accept_rate", m).set(
                    self._spec_accepted / self._spec_drafted)
            fl = self._obs.flight
            if (fl is not None and self._spec_win[0] >= 64
                    and self._spec_win[1] / self._spec_win[0] < 0.05):
                fl.trigger("spec_draft_collapse", t,
                           accept_rate=self._spec_win[1] / self._spec_win[0],
                           drafted=self._spec_win[0])
        if self._spec_win[0] >= 64:
            self._spec_win = [0, 0]

    def drain_finished(self) -> List[GenResult]:
        out, self._finished = self._finished, []
        return out

    def run(self, requests: List[Request], max_steps: int = 100000
            ) -> List[GenResult]:
        """Synchronous convenience wrapper: serve everything to completion."""
        for r in requests:
            self.submit(r)
        results: List[GenResult] = []
        steps = 0
        while self.has_work() and steps < max_steps:
            results.extend(self.step())
            steps += 1
        return results

    # -- batched first-token sampling -------------------------------------
    def _finish_first_tokens(self) -> None:
        """Drain ``_pending_first``: every slot whose prefill completed
        this step samples its first token in ONE fused dispatch (stacked
        final-chunk logits rows, per-slot params/keys gathered from the
        device state) and activates its decode row. Replaces the old
        per-slot ``_sample_one`` round-trips."""
        pend, self._pending_first = self._pending_first, []
        if not pend:
            return
        n = len(pend)
        nb = 1                           # pow2 pad bounds retraces by count
        while nb < n:
            nb *= 2
        idx = np.full((nb,), self.max_batch, np.int32)   # max_batch: pad
        pos_vals = np.zeros((nb,), np.int32)
        rows = []
        for j, (slot, logits) in enumerate(pend):
            idx[j] = slot.idx
            pos_vals[j] = slot.filled
            rows.append(logits)
        rows.extend([jnp.zeros_like(rows[0])] * (nb - n))
        stacked = jnp.concatenate(rows, axis=0)
        toks, bits, self._dstate = self._first_fn(
            self._dstate, stacked, jnp.asarray(idx), jnp.asarray(pos_vals),
            self._stack_tables(pend, nb))
        with phase("engine.sync"):
            # servelint: disable=SL002 -- first-token ids must reach the host here
            toks, bits = jax.device_get((toks, bits))
        # TTFT stops here: the first token has reached the host
        t = time.perf_counter()
        tracer = self._obs.tracer if self._obs is not None else None
        for j, (slot, _) in enumerate(pend):
            tok = int(toks[j])
            uid = slot.req.uid
            slot.res.ttft = t - slot.req.arrival_t
            slot.res.new_tokens.append(tok)
            self._deltas.append((uid, tok))
            slot.prefilling = False
            if tracer is not None:
                tracer.on_first_token(uid, t)
            self._consume_reason(slot, t, int(bits[j]))

    def _stack_tables(self, pend, nb: int):
        """Paged hook: block tables to sync into the device state when
        the pending slots activate (None on the dense engine)."""
        return None

    # -- termination ------------------------------------------------------
    def _consume_reason(self, s: "_Slot", t: float, reason: int) -> bool:
        """Book a DEVICE-REPORTED finish reason (``FINISH_EOS`` /
        ``FINISH_MAX_NEW`` / ``FINISH_ROOM`` bits; 0: still going). The
        device already retired the row; the host's only original
        contribution is the wall-clock deadline it alone can see. Pure
        bookkeeping — no token-value re-derivation, no device sync."""
        timed_out = (s.req.deadline_s is not None and
                     t - s.req.arrival_t > s.req.deadline_s)
        if reason == 0 and not timed_out:
            return False
        s.res.latency = t - s.req.arrival_t
        s.res.completed = (bool(reason & (FINISH_EOS | FINISH_MAX_NEW))
                           and not timed_out)
        s.res.timed_out = timed_out
        self._finished.append(s.res)
        self._release(s)
        self._clear_slot(s)
        return True

    def _clear_slot(self, s: "_Slot") -> None:
        if s.req is not None:
            self._by_uid.pop(s.req.uid, None)
        self._dstate = self._deactivate_fn(self._dstate, s.idx)
        s.done = True
        s.req = None
        s.prefilling = False
        s.prompt = []
        s.filled = 0
        s.spec_ok = False

    # -- admission (state only; compute happens in _prefill_step) ---------
    @staticmethod
    def _bucket(n: int) -> int:
        """Power-of-2 length bucket (floor, min 8) so prefill compiles a
        bounded number of specializations. Prompts are truncated from the
        left to the bucket (kept suffix), which preserves the systems
        metrics this engine exists to measure."""
        b = 8
        while b * 2 <= n:
            b *= 2
        return b

    @staticmethod
    def _bucket_up(n: int) -> int:
        """Power-of-2 ceiling bucket (min 8): prefill CHUNKS and the
        paged suffix pad up instead of truncating, so prompt tokens keep
        their absolute positions."""
        b = 8
        while b < n:
            b *= 2
        return b

    def _occupy(self, slot: "_Slot", req: Request, prompt: List[int],
                filled: int, cached: int = 0) -> None:
        """Claim a slot for ``req`` with its prefill cursor at
        ``filled`` (prefix hits start past the cached tokens). The
        slot's device-state row is loaded here (sampling fields + the
        uid-level PRNG fold) by one jitted index op; the row activates
        only when its first token lands."""
        slot.req = req
        slot.res = GenResult(uid=req.uid, prompt_len=len(prompt),
                             cached_tokens=cached)
        slot.prompt = prompt
        slot.filled = filled
        slot.pos = filled
        slot.prefilling = True
        slot.done = False
        # dense draft cache has a row per slot; the paged _begin replaces
        # this with the outcome of its draft-pool lease
        slot.spec_ok = self.spec is not None
        slot.order = self._order
        self._order += 1
        sp = req.sampling
        self._dstate = self._occupy_fn(
            self._dstate, slot.idx, self._base_key, np.int32(req.uid),
            np.float32(sp.temperature), np.int32(sp.top_k),
            np.float32(sp.top_p),
            np.int32(-1 if sp.eos_id is None else sp.eos_id),
            np.int32(sp.max_new_tokens), np.int32(filled),
            np.int32(req.prefix_draws))
        self._by_uid[req.uid] = slot
        if self._obs is not None:
            # admit event: queue wait ends here (a span opens lazily for
            # requests that never passed a frontend submit)
            self._obs.tracer.on_admit(req.uid, time.perf_counter(),
                                      arrival_t=req.arrival_t,
                                      model=self._obs.model,
                                      backend=self._obs.backend)

    @staticmethod
    def _decode_budget(req: Request) -> int:
        """Tokens the request may still draw: a retried request already
        emitted ``prefix_draws`` of its ``max_new_tokens`` (they ride in
        its prompt now), so only the remainder needs cache room."""
        return max(req.sampling.max_new_tokens - req.prefix_draws, 1)

    def _begin(self, slot_id: int, req: Request) -> bool:
        prompt = req.tokens[-(self.max_seq - self._decode_budget(req) - 1):]
        if req.prefix_draws == 0:
            prompt = prompt[-self._bucket(len(prompt)):]
        # a RETRY skips the pow2 truncation: its prompt is the original
        # (already bucketed) prompt plus the emitted chain — truncating
        # again would shift token positions off the unfailed run's
        self._occupy(self._slots[slot_id], req, prompt, filled=0)
        return True

    # -- prefill ----------------------------------------------------------
    def _prefill_step(self, slot_id: int, slot: "_Slot",
                      rem: Optional[int]) -> Optional[int]:
        """Advance one slot's prefill cursor by (up to) one chunk; on the
        last chunk, sample the request's first token. Returns the
        remaining token budget."""
        req, res = slot.req, slot.res
        t = time.perf_counter()
        # deadline sweep at the chunk boundary: budget must not be burnt
        # prefilling a request that already missed its deadline
        if req.deadline_s is not None and t - req.arrival_t > req.deadline_s:
            res.latency = t - req.arrival_t
            res.timed_out = True
            res.completed = False
            self._finished.append(res)
            self._release(slot)
            self._clear_slot(slot)
            return rem
        remaining = len(slot.prompt) - slot.filled
        if self._chunkable():
            n = min(self.chunk_tokens, remaining)
            if rem is not None:
                n = max(1, min(n, rem))
        else:
            n = remaining              # whole-prompt prefill is atomic; it
            #                            may overdraw the budget (rem goes
            #                            negative and the loop stops)
        logits = self._prefill_chunk(slot_id, slot, n)
        slot.filled += n
        slot.pos = slot.filled
        res.prefill_chunks += 1
        if self._obs is not None:
            self._obs.tracer.on_chunk(req.uid, time.perf_counter(), n)
            self._obs.registry.counter("engine_prefill_chunks",
                                       self._obs.model).inc()
        if rem is not None:
            rem -= n
        if slot.filled >= len(slot.prompt):
            self._finish_prefill(slot, logits)
        return rem

    def _prefill_chunk(self, slot_id: int, slot: "_Slot", n: int):
        """Run the model over ``n`` prompt tokens at the cursor; returns
        the last live token's logits (meaningful on the final chunk)."""
        if not self._chunkable():
            return self._whole_prefill(slot_id, slot)
        start = slot.filled
        chunk = slot.prompt[start:start + n]
        sb = self._bucket_up(n)
        padded = np.zeros((1, sb), np.int32)
        padded[0, :n] = chunk
        ctx = self._gather_slot(self.cache, jnp.int32(slot_id))
        logits, new_kv = self._chunk_prefill(self.params, jnp.asarray(padded),
                                             ctx, jnp.int32(start),
                                             jnp.int32(n))
        self.cache = self._scatter_slot(self.cache, new_kv, jnp.int32(slot_id),
                                        jnp.int32(start), jnp.int32(n))
        return logits

    def _whole_prefill(self, slot_id: int, slot: "_Slot"):
        """Legacy one-shot prefill + whole-row insert (non-chunkable
        families, and ``chunk_tokens=None`` where it skips the per-chunk
        gather)."""
        req = slot.req
        batch = {"tokens": jnp.asarray(np.asarray(slot.prompt, np.int32)[None])}
        if self.cfg.family == "encdec":
            se = (req.src_embeds if req.src_embeds is not None
                  else np.zeros((self.cfg.frontend_seq, self.cfg.d_model),
                                np.float32))
            batch["src_embeds"] = jnp.asarray(se[None])
        logits, rcache = self._prefill(self.params, batch)
        self.cache = self._insert(self.cache, rcache, slot_id)
        return logits

    def _finish_prefill(self, slot: "_Slot", logits) -> None:
        """The last chunk just ran: register the prefix and queue the slot
        for this step's BATCHED first-token sample
        (``_finish_first_tokens`` — one fused dispatch for every prefill
        that completed this step; TTFT is stamped when the token reaches
        the host there). The usual termination rules apply when the
        token lands (max_new_tokens=1 must return exactly one token, an
        EOS straight out of prefill must stop generation)."""
        self._register_prefix(slot)
        if slot.spec_ok:
            # draft residency secured at admission: give the draft its
            # whole-prompt KV now, off the guarded decode path
            self._spec_prefill_slot(slot)
        self._pending_first.append((slot, logits))

    def _register_prefix(self, slot: "_Slot") -> None:
        """Paged hook: register completed full blocks for prefix reuse."""


# ---------------------------------------------------------------------------
# paged engine


DEFAULT_BLOCK_SIZE = 16


class PagedInferenceEngine(InferenceEngine):
    """Continuous-batching engine over a paged (block-pool) KV cache.

    Differences from the dense engine:
      * one global pool of ``num_blocks`` KV blocks instead of a dense
        (max_batch, max_seq) cache — admission is gated on free blocks,
        blocks are freed the step a request finishes;
      * a radix prefix cache: the cached prefix of a prompt (multi-turn
        history, shared system prompt) is leased by refcount and only the
        uncached suffix is prefilled — this is where the TTFT win on
        shared-prefix traffic comes from. The lookup runs at admission
        (leases protect the prefix from eviction, gating counts only the
        blocks actually needed) and again at first-chunk time as an
        EXTENSION, so a prompt admitted while its twin is still
        prefilling adopts every full block the twin registers chunk by
        chunk;
      * prompts are NOT bucket-truncated (truncation would shift token
        positions and break prefix identity); instead each prefill chunk
        is right-padded to a power-of-2 bucket and masked, which bounds
        compile specializations the same way.
    """

    paged = True

    def __init__(self, cfg: ModelConfig, params, backend: BackendProfile,
                 max_seq: int = 512, seed: int = 0, fns=None,
                 block_size: int = DEFAULT_BLOCK_SIZE,
                 num_blocks: Optional[int] = None,
                 prefix_cache: bool = True,
                 chunk_tokens: Optional[int] = None,
                 step_token_budget: Optional[int] = None,
                 decode_burst: int = 1, obs=None,
                 spec: Optional[SpecDraft] = None, fault=None):
        if not supports_paged(cfg):
            raise ValueError(f"{cfg.name}: family/attention has no paged path")
        if max_seq % block_size:
            raise ValueError(f"max_seq {max_seq} % block_size {block_size}")
        self.block_size = block_size
        self.blocks_per_seq = max_seq // block_size
        self.num_blocks = num_blocks or backend.max_batch * self.blocks_per_seq
        if self.num_blocks < self.blocks_per_seq:
            raise ValueError("pool smaller than one full sequence")
        self.pool = BlockPool(self.num_blocks, block_size)
        self.prefix: Optional[RadixPrefixCache] = (
            RadixPrefixCache(self.pool) if prefix_cache else None)
        self.hit_tokens = 0                       # prefix tokens NOT prefilled
        self.prompt_tokens = 0
        super().__init__(cfg, params, backend, max_seq, seed, fns,
                         chunk_tokens=chunk_tokens,
                         step_token_budget=step_token_budget,
                         decode_burst=decode_burst, obs=obs, spec=spec,
                         fault=fault)

    # -- hooks ----------------------------------------------------------
    def _make_slot(self) -> _PagedSlot:
        return _PagedSlot()

    def _init_cache(self):
        return init_paged_cache(self.cfg, self.num_blocks, self.block_size,
                                self._kv_dtype)

    def _register_cache_bytes(self) -> None:
        # measured block width: pool tensor bytes / population — int8
        # pools (quantized k/v + f32 scales) come out at true width
        self.pool.bytes_per_block = self._cache_bytes // self.num_blocks

    def _init_dstate(self):
        # per-row block tables ride in the device state so the fused
        # decode never re-stages them from host
        return init_device_state(self.max_batch, self.blocks_per_seq)

    def _compile(self) -> PagedCompiledFns:
        return compile_paged_fns(self.cfg, self.backend, self.max_seq,
                                 self.block_size)

    def _bind_fns(self) -> None:
        self._gather = self.fns.gather
        self._prefill = self.fns.prefill
        self._scatter = self.fns.scatter
        self._decode = self.fns.decode
        self._copy = self.fns.copy
        self._bind_fused()

    def _chunkable(self) -> bool:
        # the paged prefill is ALWAYS a chunk-append (gather/compute/
        # scatter); chunk_tokens only bounds how much one pass covers
        return self.chunk_tokens is not None

    # -- speculative decoding (paged residency) -------------------------
    def _build_spec_cache(self, spec: SpecDraft):
        """Draft KV storage: its own small block pool. Same block size
        and (by default) population as the target's, but each block is
        the DRAFT's width — for a 10x smaller draft that is ~10x fewer
        bytes. ``spec.num_blocks`` overrides the population (the
        KV-pressure test knob)."""
        self.spec_blocks = spec.num_blocks or self.num_blocks
        if self.spec_blocks < self.blocks_per_seq:
            return None
        return init_paged_cache(spec.cfg, self.spec_blocks, self.block_size,
                                self._kv_dtype)

    def _init_spec(self) -> None:
        super()._init_spec()
        if self.spec is None:             # KV-pressure gate refused
            return
        self.spec_pool = BlockPool(self.spec_blocks, self.block_size)
        # device-resident draft block tables: updated by a jitted row op
        # at admission (off the guarded decode path), read by every
        # verify dispatch — never re-staged from host per step
        self._spec_tables = jnp.zeros((self.max_batch, self.blocks_per_seq),
                                      jnp.int32)

    def _compile_spec(self, spec: SpecDraft) -> SpecFns:
        return compile_spec_fns(self.cfg, spec.cfg, self.max_seq, spec.k,
                                self.block_size)

    def _spec_dispatch(self):
        return self.sfns.step(self.params, self.spec.params, self.cache,
                              self._spec_cache, self._dstate,
                              self._spec_tables)

    def _spec_prefill_slot(self, slot: _PagedSlot) -> None:
        n = slot.filled
        sb = self._bucket_up(n)
        padded = np.zeros((1, sb), np.int32)
        padded[0, :n] = slot.prompt[:n]
        stab = np.zeros((self.blocks_per_seq,), np.int32)
        stab[:len(slot.spec_blocks)] = slot.spec_blocks
        # start=0: no cached draft context — gather one block for shape
        ctx_kv = self._sgather(self._spec_cache, jnp.asarray(stab[:1]))
        _, new_kv = self._sprefill(self.spec.params, jnp.asarray(padded),
                                   ctx_kv, jnp.int32(0), jnp.int32(n))
        self._spec_cache = self._sscatter(self._spec_cache, new_kv,
                                          jnp.asarray(stab), jnp.int32(0),
                                          jnp.int32(n))

    def _stack_tables(self, pend, nb: int):
        """Sync each activating slot's (possibly extension-rewritten)
        block table into the device state alongside its first token —
        the one point every mid-prefill table edit funnels through."""
        t = np.zeros((nb, self.blocks_per_seq), np.int32)
        for j, (slot, _) in enumerate(pend):
            t[j] = slot.table
        return jnp.asarray(t)

    # -- capacity -------------------------------------------------------
    def kv_free_frac(self) -> float:
        """Allocatable fraction of the pool — evictable prefix-cache
        blocks count as free (they are reclaimed on demand)."""
        free = self.pool.num_free
        if self.prefix:
            free += self.prefix.evictable_blocks()
        return free / self.num_blocks

    def kv_used_frac(self) -> float:
        return self.pool.used_frac

    def _slot_kv_bytes(self, slot: _PagedSlot) -> int:
        return len(slot.blocks) * self.pool.bytes_per_block

    def kv_pool_bytes(self) -> Tuple[int, int]:
        """(used, free) bytes over the block population; evictable
        prefix-cache blocks count as used until actually reclaimed."""
        return self.pool.used_bytes, self.pool.free_bytes

    def prefix_hit_rate(self) -> float:
        return self.hit_tokens / self.prompt_tokens if self.prompt_tokens else 0.0

    def prefix_peek(self, req: Request) -> int:
        """Cached-prefix tokens this request would reuse if admitted now
        (same prompt capping as admission). 0 without a prefix cache."""
        if not self.prefix:
            return 0
        prompt = req.tokens[-(self.max_seq - self._decode_budget(req) - 1):]
        return min(self.prefix.peek(prompt), max(len(prompt) - 1, 0))

    def block_capacity(self) -> int:
        """Worst-case admissions the pool can still back (a request may
        need blocks_per_seq fresh blocks; evictable cache blocks count)."""
        blocks_free = self.pool.num_free
        if self.prefix:
            blocks_free += self.prefix.evictable_blocks()
        return blocks_free // self.blocks_per_seq

    def free_slots(self) -> int:
        """Admission capacity: free decode slots AND block headroom."""
        cap = min(self.idle_slots(), self.block_capacity())
        return max(0, cap - len(self._queue))

    # -- admission ------------------------------------------------------
    def _begin(self, slot_id: int, req: Request) -> bool:
        bs = self.block_size
        budget = self._decode_budget(req)
        prompt = req.tokens[-(self.max_seq - budget - 1):]
        plen = len(prompt)
        total = min(plen + budget, self.max_seq)
        # prefix lookup AT ADMISSION: the leases protect the matched
        # blocks from the eviction below (a repeat prompt must never
        # evict its own cached prefix to make room for itself), and the
        # gating counts only the blocks actually needed — a mostly-
        # cached prompt admits on a nearly-full pool
        matched, keep, cow_src = self._match_prefix(prompt)
        n_need = math.ceil(total / bs) - len(matched)
        short = n_need - self.pool.num_free
        if short > 0 and self.prefix is not None:
            self.prefix.evict(short)
        if n_need > self.pool.num_free:
            for b in matched:             # out of blocks: stay queued
                self.pool.decref(b)
            if cow_src is not None:
                self.pool.decref(cow_src)
            return False
        fresh = self.pool.alloc_many(n_need)
        owned = matched + fresh
        slot = self._slots[slot_id]
        # leak guard: from here to the end of admission the slot holds
        # leases that are not yet reachable through _release — a raise
        # (device OOM in the COW copy / occupy index op) must hand every
        # block back and leave the slot reusable, or the pool leaks its
        # way to a wedged replica
        try:
            if cow_src is not None:       # copy-on-write the shared tail
                self.cache = self._copy(self.cache, jnp.int32(cow_src),
                                        jnp.int32(fresh[0]))
                self.pool.decref(cow_src)
                cow_src = None
            table = np.zeros((self.blocks_per_seq,), np.int32)
            table[:len(owned)] = owned
            self._occupy(slot, req, prompt, filled=keep, cached=keep)
            slot.table = table
            slot.blocks = owned
            self.hit_tokens += keep
            self.prompt_tokens += plen
            # draft residency: lease the request's full span from the
            # draft pool (no prefix sharing there — the draft prefills
            # the whole prompt itself). A dry draft pool is NOT an
            # admission failure: the slot runs plain stepwise (spec_ok
            # False falls the whole batch back) rather than stalling
            # the target.
            slot.spec_ok = False
            if self.spec is not None:
                n_blk = math.ceil(total / bs)
                if n_blk <= self.spec_pool.num_free:
                    slot.spec_blocks = self.spec_pool.alloc_many(n_blk)
                    stab = np.zeros((self.blocks_per_seq,), np.int32)
                    stab[:n_blk] = slot.spec_blocks
                    self._spec_tables = self.sfns.set_table(
                        self._spec_tables, slot.idx, jnp.asarray(stab))
                    slot.spec_ok = True
        except BaseException:
            for b in owned:
                self.pool.decref(b)
            if cow_src is not None:
                self.pool.decref(cow_src)
            for b in slot.spec_blocks:
                self.spec_pool.decref(b)
            slot.table = None
            slot.blocks = []
            slot.spec_blocks = []
            if slot.req is req:           # roll back a partial occupy
                self._clear_slot(slot)
                slot.res = None
            raise
        return True

    def _match_prefix(self, prompt: List[int]):
        """Longest cached prefix of ``prompt`` trimmed to reusable form:
        always recompute >= 1 token (the last token's logits seed
        generation), so a fully-cached prompt keeps ``plen - 1``.
        Returns ``(leased full blocks, keep tokens, cow_src)`` —
        ``cow_src`` is a leased partially-needed block the caller must
        copy-on-write into an owned block (or decref)."""
        if self.prefix is None:
            return [], 0, None
        bs = self.block_size
        plen = len(prompt)
        matched, m = self.prefix.match(prompt)
        keep = min(m, plen - 1)
        n_keep = keep // bs
        cow_src = None
        if keep < m:                      # match overshoots the kept run
            if keep % bs:
                cow_src = matched[n_keep]      # partial block -> COW
                drop = matched[n_keep + 1:]
            else:
                drop = matched[n_keep:]
            for b in drop:
                self.pool.decref(b)
            matched = matched[:n_keep]
        return matched, keep, cow_src

    # -- prefill --------------------------------------------------------
    def _extend_prefix(self, slot: _PagedSlot) -> None:
        """Chunk-boundary re-lookup: adopt full blocks a concurrent twin
        registered since this slot's LAST prefill pass (progressive
        chunk-by-chunk sharing — a twin that finishes registering while
        this request is mid-prefill is picked up at the next boundary,
        not just at first-chunk time). Aligned extension only — when
        admission copy-on-wrote a partial tail, what it decided stands;
        an unaligned cursor also skips (adoption would orphan the
        partial block's freshly-written KV)."""
        if self.prefix is None or slot.filled % self.block_size:
            return
        bs = self.block_size
        prompt, plen = slot.prompt, len(slot.prompt)
        n0 = slot.filled // bs
        matched, m = self.prefix.match(prompt)
        n_keep = min(m, plen - 1) // bs
        if n_keep > n0:
            for b in slot.blocks[n0:n_keep]:   # fresh blocks now covered
                self.pool.decref(b)
            slot.blocks[n0:n_keep] = matched[n0:n_keep]
            slot.table[n0:n_keep] = matched[n0:n_keep]
            gained = n_keep * bs - slot.filled
            slot.filled = n_keep * bs
            slot.pos = slot.filled
            slot.res.cached_tokens += gained
            self.hit_tokens += gained
            adopted = set(range(n0, n_keep))
            for i, b in enumerate(matched):    # release unadopted leases
                if i not in adopted:
                    self.pool.decref(b)
        else:
            for b in matched:
                self.pool.decref(b)

    def _prefill_chunk(self, slot_id: int, slot: _PagedSlot, n: int):
        bs = self.block_size
        start = slot.filled
        chunk = slot.prompt[start:start + n]
        sb = self._bucket_up(n)
        padded = np.zeros((1, sb), np.int32)
        padded[0, :n] = chunk
        # pow2 bound on the table entries holding CACHED context (the
        # chunk attends itself inside the compute core), so the gather
        # reads ~the cached prefix, not the full max_seq span
        ctx = 1
        while ctx * bs < start:
            ctx *= 2
        ctx = min(ctx, self.blocks_per_seq)
        ctx_kv = self._gather(self.cache, jnp.asarray(slot.table[:ctx]))
        logits, new_kv = self._prefill(self.params, jnp.asarray(padded),
                                       ctx_kv, jnp.int32(start), jnp.int32(n))
        self.cache = self._scatter(self.cache, new_kv,
                                   jnp.asarray(slot.table), jnp.int32(start),
                                   jnp.int32(n))
        return logits

    def _prefill_step(self, slot_id: int, slot: _PagedSlot,
                      rem: Optional[int]) -> Optional[int]:
        # extension lookup at EVERY chunk boundary, before the base
        # class sizes the chunk: blocks a twin registered since the last
        # pass move the cursor, so only the remainder is charged (the
        # radix lookup is host-side and O(matched tokens) — cheap next
        # to the chunk it can save)
        self._extend_prefix(slot)
        rem = super()._prefill_step(slot_id, slot, rem)
        # register full blocks the moment their KV is valid (the radix
        # insert dedupes), so a twin prompt admitted in the same step
        # reuses this one's blocks chunk by chunk instead of waiting for
        # the whole prefill to land
        if not slot.done and slot.prefilling:
            self._register_prefix(slot)
        return rem

    def _register_prefix(self, slot: _PagedSlot) -> None:
        if self.prefix is not None and slot.filled >= self.block_size:
            n_full = slot.filled // self.block_size
            self.prefix.insert(slot.prompt[:n_full * self.block_size],
                               slot.table[:n_full].tolist())

    # -- reap -----------------------------------------------------------
    def _release(self, slot: _PagedSlot, register_prefix: bool = True) -> None:
        if slot.table is None:
            return
        super()._release(slot, register_prefix)   # account KV bytes first
        if register_prefix and self.prefix is not None and slot.res is not None:
            # everything written (prompt + generated-but-last) is valid
            # KV; register its full blocks for future prefix hits
            seq = (slot.prompt + slot.res.new_tokens)[: slot.pos]
            n_full = len(seq) // self.block_size
            if n_full:
                self.prefix.insert(seq, slot.table[:n_full].tolist())
        for b in slot.blocks:
            self.pool.decref(b)
        for b in slot.spec_blocks:        # draft co-retires with target
            self.spec_pool.decref(b)
        slot.table = None
        slot.blocks = []
        slot.spec_blocks = []
        slot.spec_ok = False
