"""Decoder-only LM trunk (dense / MoE / MLA / VLM backbones).

Layers are stacked along a leading axis and executed under ``lax.scan``
(HLO stays small at 64 layers). MoE models with ``first_dense_layers``
unroll the dense prefix and scan the homogeneous MoE stack.

Three entry points per model:
  ``lm_forward``  — full causal forward (training), returns (logits, aux)
  ``lm_prefill``  — causal forward + populated KV cache, last-token logits
  ``lm_decode``   — one-token step against the cache
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models.common import (Params, embed_init, init_rmsnorm,
                                 mrope_cos_sin, rmsnorm, rope_cos_sin,
                                 stack_init)
from repro.models.mlp import ffn, init_ffn
from repro.models.moe import init_moe, moe_ffn


# ---------------------------------------------------------------------------
# init


def _init_block(cfg: ModelConfig, key, moe: bool, dtype):
    k1, k2 = jax.random.split(key)
    init_attn = attn.init_mla if cfg.attention_type == "mla" else attn.init_gqa
    return {
        "attn_norm": init_rmsnorm(cfg.d_model, dtype),
        "attn": init_attn(cfg, k1, dtype),
        "ffn_norm": init_rmsnorm(cfg.d_model, dtype),
        "ffn": init_moe(cfg, k2, dtype) if moe else init_ffn(cfg, k2, dtype=dtype),
    }


def init_lm(cfg: ModelConfig, key, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 4)
    n_prefix = cfg.first_dense_layers if cfg.has_moe else 0
    n_stack = cfg.num_layers - n_prefix
    p: Params = {
        "embed": embed_init(ks[0], cfg.vocab_size, cfg.d_model, dtype),
        "final_norm": init_rmsnorm(cfg.d_model, dtype),
        "layers": stack_init(
            ks[1], n_stack,
            lambda k: _init_block(cfg, k, moe=cfg.has_moe, dtype=dtype)),
    }
    if n_prefix:
        pk = jax.random.split(ks[2], n_prefix)
        p["prefix_layers"] = [
            _init_block(cfg, k, moe=False, dtype=dtype) for k in pk]
    if not cfg.tie_embeddings:
        p["lm_head"] = embed_init(ks[3], cfg.vocab_size, cfg.d_model, dtype)
    return p


# ---------------------------------------------------------------------------
# positions / rope tables


def _cos_sin(cfg: ModelConfig, positions: jnp.ndarray):
    """positions: (..., S) ints or (..., S, 3) M-RoPE triplets."""
    hd = cfg.head_dim if cfg.attention_type != "mla" else cfg.qk_rope_head_dim
    if cfg.mrope_sections:
        if positions.ndim >= 2 and positions.shape[-1] == 3:
            return mrope_cos_sin(positions, cfg.mrope_sections, cfg.rope_theta)
        # text-only positions: t == h == w
        trip = jnp.stack([positions] * 3, axis=-1)
        return mrope_cos_sin(trip, cfg.mrope_sections, cfg.rope_theta)
    return rope_cos_sin(positions, hd, cfg.rope_theta)


def _block_train(cfg: ModelConfig, moe: bool, q_chunk: int, moe_cf=1.25):
    def body(lp, h, cos, sin):
        x = rmsnorm(lp["attn_norm"], h, cfg.norm_eps)
        if cfg.attention_type == "mla":
            h = h + attn.mla_full(lp["attn"], cfg, x, cos, sin, q_chunk=q_chunk)
        else:
            h = h + attn.gqa_full(lp["attn"], cfg, x, cos, sin, q_chunk=q_chunk)
        x = rmsnorm(lp["ffn_norm"], h, cfg.norm_eps)
        if moe:
            y, aux = moe_ffn(lp["ffn"], cfg, x, capacity_factor=moe_cf)
            return h + y, aux
        return h + ffn(lp["ffn"], cfg, x), jnp.zeros((), jnp.float32)
    return body


# ---------------------------------------------------------------------------
# forward (train)


def embed_tokens(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                 extra_embeds: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    h = params["embed"][tokens].astype(_adtype(cfg))
    if extra_embeds is not None:
        h = jnp.concatenate([extra_embeds.astype(h.dtype), h], axis=1)
    return h


def _adtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def unembed(params: Params, cfg: ModelConfig, h: jnp.ndarray) -> jnp.ndarray:
    w = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = h.astype(jnp.float32) @ w.astype(jnp.float32).T
    if cfg.logit_softcap:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return logits


def lm_forward(
    params: Params, cfg: ModelConfig, tokens: jnp.ndarray, *,
    positions: Optional[jnp.ndarray] = None,
    extra_embeds: Optional[jnp.ndarray] = None,
    q_chunk: int = 512, remat: bool = True, moe_cf=1.25,
    return_hidden: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full causal forward. Returns (logits (B,S,V), moe aux loss);
    ``return_hidden`` skips the unembedding (chunked-CE training path)."""
    h = embed_tokens(params, cfg, tokens, extra_embeds)
    B, S, _ = h.shape
    if positions is None:
        positions = jnp.arange(S)[None, :].repeat(B, 0)
    cos, sin = _cos_sin(cfg, positions)
    aux_total = jnp.zeros((), jnp.float32)

    body = _block_train(cfg, moe=False, q_chunk=q_chunk)
    for lp in params.get("prefix_layers", []):
        h, _ = body(lp, h, cos, sin)

    moe_body = _block_train(cfg, moe=cfg.has_moe, q_chunk=q_chunk, moe_cf=moe_cf)

    def scan_body(carry, lp):
        h, aux = carry
        h, a = moe_body(lp, h, cos, sin)
        return (h, aux + a), None

    if remat:
        scan_body = jax.checkpoint(scan_body)
    (h, aux_total), _ = jax.lax.scan(scan_body, (h, aux_total), params["layers"])
    h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    if return_hidden:
        return h, aux_total
    return unembed(params, cfg, h), aux_total


# ---------------------------------------------------------------------------
# prefill


def lm_prefill(
    params: Params, cfg: ModelConfig, tokens: jnp.ndarray, cache_len: int, *,
    positions: Optional[jnp.ndarray] = None,
    extra_embeds: Optional[jnp.ndarray] = None,
    q_chunk: int = 512, moe_cf=1.25,
) -> Tuple[jnp.ndarray, Params]:
    """Returns (last-token logits (B,V), stacked cache)."""
    h = embed_tokens(params, cfg, tokens, extra_embeds)
    B, S, _ = h.shape
    if positions is None:
        positions = jnp.arange(S)[None, :].repeat(B, 0)
    cos, sin = _cos_sin(cfg, positions)
    eff_len = cache_len if cfg.sliding_window is None else cfg.sliding_window

    def block_prefill(lp, h):
        x = rmsnorm(lp["attn_norm"], h, cfg.norm_eps)
        if cfg.attention_type == "mla":
            o, cache = attn.mla_prefill(lp["attn"], cfg, x, cos, sin, eff_len,
                                        q_chunk=q_chunk)
        else:
            o, cache = attn.gqa_prefill(lp["attn"], cfg, x, cos, sin, eff_len,
                                        q_chunk=q_chunk)
        h = h + o
        x = rmsnorm(lp["ffn_norm"], h, cfg.norm_eps)
        if cfg.has_moe and "router" in lp["ffn"]:
            y, _ = moe_ffn(lp["ffn"], cfg, x, capacity_factor=moe_cf)
        else:
            y = ffn(lp["ffn"], cfg, x)
        return h + y, cache

    prefix_caches = []
    for lp in params.get("prefix_layers", []):
        h, c = block_prefill(lp, h)
        prefix_caches.append(c)

    def scan_body(h, lp):
        h, cache = block_prefill(lp, h)
        return h, cache

    h, stack_cache = jax.lax.scan(scan_body, h, params["layers"])
    h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = unembed(params, cfg, h[:, -1])
    cache = {"stack": stack_cache}
    if prefix_caches:
        cache["prefix"] = prefix_caches
    return logits, cache


# ---------------------------------------------------------------------------
# decode


def lm_decode(
    params: Params, cfg: ModelConfig, token: jnp.ndarray, cache: Params,
    pos, *, positions: Optional[jnp.ndarray] = None, moe_cf=None,
) -> Tuple[jnp.ndarray, Params]:
    """One-token step. token: (B, 1) int32; pos: scalar int32 global index.
    Returns (logits (B, V), new cache)."""
    h = params["embed"][token].astype(_adtype(cfg))
    B = h.shape[0]
    if positions is None:
        p_ = jnp.asarray(pos, jnp.int32)
        positions = (jnp.full((B, 1), p_) if p_.ndim == 0 else p_[:, None])
    cos, sin = _cos_sin(cfg, positions)

    def block_decode(lp, h, c):
        x = rmsnorm(lp["attn_norm"], h, cfg.norm_eps)
        if cfg.attention_type == "mla":
            o, c = attn.mla_decode(lp["attn"], cfg, x, cos, sin, c, pos)
        else:
            o, c = attn.gqa_decode(lp["attn"], cfg, x, cos, sin, c, pos)
        h = h + o
        x = rmsnorm(lp["ffn_norm"], h, cfg.norm_eps)
        if cfg.has_moe and "router" in lp["ffn"]:
            y, _ = moe_ffn(lp["ffn"], cfg, x, capacity_factor=moe_cf)
        else:
            y = ffn(lp["ffn"], cfg, x)
        return h + y, c

    new_prefix = []
    for lp, c in zip(params.get("prefix_layers", []), cache.get("prefix", [])):
        h, c = block_decode(lp, h, c)
        new_prefix.append(c)

    def scan_body(h, xs):
        lp, c = xs
        h, c = block_decode(lp, h, c)
        return h, c

    h, new_stack = jax.lax.scan(scan_body, h, (params["layers"], cache["stack"]))
    h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = unembed(params, cfg, h[:, -1])
    new_cache = {"stack": new_stack}
    if new_prefix:
        new_cache["prefix"] = new_prefix
    return logits, new_cache


# ---------------------------------------------------------------------------
# paged prefill / decode (block-pool KV cache; see serving/kvpool.py)
#
# Attention inside these trunks is dispatched per backend through the
# kernel registry (kernels/ops.kernel_mode): the Mosaic Pallas
# paged_decode_attention / paged_prefill_attention kernels on TPU,
# interpret-executed kernels for kernel tests, and the jnp reference math
# on CPU. The dispatch decision is read at trace time, i.e. once per
# compiled engine step — not per token.


def supports_paged(cfg: ModelConfig) -> bool:
    """Paged caching covers the GQA transformer trunk (dense + MoE).
    MLA/SSM/hybrid/enc-dec state and ring-buffer windows keep their dense
    layouts; sequences there fall back to the dense engine."""
    return (cfg.family in ("dense", "moe") and cfg.attention_type == "gqa"
            and cfg.sliding_window is None)


def supports_chunked(cfg: ModelConfig) -> bool:
    """Chunked (token-budget) prefill needs an append-able linear KV
    layout: the GQA trunk qualifies on BOTH cache disciplines (paged
    block tables and the dense per-slot cache share ``lm_chunk_prefill``
    via their gather/scatter pairs). Ring-buffer sliding windows, MLA
    latent caches and SSM/enc-dec state fall back to whole-prompt
    prefill — the engine still schedules them under the same token
    budget, as one maximal chunk."""
    return supports_paged(cfg)


def lm_paged_prefill(
    params: Params, cfg: ModelConfig, tokens: jnp.ndarray, ctx_kv: Params,
    start, s_real, *, moe_cf=1.25,
) -> Tuple[jnp.ndarray, Params]:
    """Compute pass of a paged suffix prefill (no pool access).

    Prefill is split in three so the pool is never re-materialized:
    ``attn.paged_gather_ctx`` reads the cached context blocks (small),
    this function runs the model over the uncached suffix against that
    gathered context, and ``attn.paged_scatter`` writes the returned
    suffix KV into the request's blocks in place (donated buffer).

    tokens: (1, Sb) suffix right-padded to a bucket; ctx_kv: gathered
    context KV (same pytree shape as the pool, block axes merged);
    start: tokens already cached (prefix hit); s_real: live suffix
    tokens. Returns (logits of the last live token (1, V), suffix KV)."""
    h = embed_tokens(params, cfg, tokens)
    _, Sb, _ = h.shape
    positions = (start + jnp.arange(Sb))[None, :]
    cos, sin = _cos_sin(cfg, positions)

    def block(lp, h, c):
        x = rmsnorm(lp["attn_norm"], h, cfg.norm_eps)
        o, kv = attn.gqa_paged_prefill(lp["attn"], cfg, x, cos, sin, c,
                                       start, s_real)
        h = h + o
        x = rmsnorm(lp["ffn_norm"], h, cfg.norm_eps)
        if cfg.has_moe and "router" in lp["ffn"]:
            y, _ = moe_ffn(lp["ffn"], cfg, x, capacity_factor=moe_cf)
        else:
            y = ffn(lp["ffn"], cfg, x)
        return h + y, kv

    new_prefix = []
    for lp, c in zip(params.get("prefix_layers", []), ctx_kv.get("prefix", [])):
        h, kv = block(lp, h, c)
        new_prefix.append(kv)

    def scan_body(h, xs):
        lp, c = xs
        h, kv = block(lp, h, c)
        return h, kv

    h, new_stack = jax.lax.scan(scan_body, h,
                                (params["layers"], ctx_kv["stack"]))
    h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    h_last = jax.lax.dynamic_slice_in_dim(h, jnp.maximum(s_real - 1, 0), 1,
                                          axis=1)[:, 0]
    new_kv = {"stack": new_stack}
    if new_prefix:
        new_kv["prefix"] = new_prefix
    return unembed(params, cfg, h_last), new_kv


# The chunk-prefill trunk is cache-layout agnostic: ``ctx_kv`` is "this
# sequence's cached KV in token order", however it was gathered — through
# a block table (attn.paged_gather_ctx) or out of a dense slot
# (attn.dense_gather_slot). Continuous batching runs a prompt through it
# one chunk at a time, advancing ``start`` per chunk.
lm_chunk_prefill = lm_paged_prefill


def lm_paged_decode(
    params: Params, cfg: ModelConfig, token: jnp.ndarray, cache: Params,
    block_tables: jnp.ndarray, pos, *, moe_cf=None,
) -> Tuple[jnp.ndarray, Params]:
    """One-token step against the block pool. token: (B, 1) int32;
    block_tables: (B, NBseq); pos: (B,) global token index, -1 for
    inactive slots. Returns (logits (B, V), updated pool)."""
    h = params["embed"][token].astype(_adtype(cfg))
    pos = jnp.asarray(pos, jnp.int32)
    positions = jnp.maximum(pos, 0)[:, None]
    cos, sin = _cos_sin(cfg, positions)

    def block(lp, h, c):
        x = rmsnorm(lp["attn_norm"], h, cfg.norm_eps)
        o, c = attn.gqa_paged_decode(lp["attn"], cfg, x, cos, sin, c,
                                     block_tables, pos)
        h = h + o
        x = rmsnorm(lp["ffn_norm"], h, cfg.norm_eps)
        if cfg.has_moe and "router" in lp["ffn"]:
            y, _ = moe_ffn(lp["ffn"], cfg, x, capacity_factor=moe_cf)
        else:
            y = ffn(lp["ffn"], cfg, x)
        return h + y, c

    new_prefix = []
    for lp, c in zip(params.get("prefix_layers", []), cache.get("prefix", [])):
        h, c = block(lp, h, c)
        new_prefix.append(c)

    def scan_body(h, xs):
        lp, c = xs
        h, c = block(lp, h, c)
        return h, c

    h, new_stack = jax.lax.scan(scan_body, h, (params["layers"], cache["stack"]))
    h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = unembed(params, cfg, h[:, -1])
    new_cache = {"stack": new_stack}
    if new_prefix:
        new_cache["prefix"] = new_prefix
    return logits, new_cache


# ---------------------------------------------------------------------------
# speculative verify (multi-token batched decode with all-position logits)
#
# The draft/verify step of speculative decoding: every batch row feeds
# its last sampled token plus K drafted tokens in ONE forward and gets
# logits back at EVERY position (the chunk-prefill trunk computes the
# full hidden state too, but unembeds only the last live token — verify
# needs them all, so these wrappers share the block/scan structure and
# differ only in the attention primitive and the final unembed).


def lm_paged_verify(
    params: Params, cfg: ModelConfig, tokens: jnp.ndarray, cache: Params,
    block_tables: jnp.ndarray, pos, max_pos=None, *, moe_cf=None,
) -> Tuple[jnp.ndarray, Params]:
    """Batched S-token verify step against the block pool.
    tokens: (B, S) int32 — row layout [last_token, draft_1..draft_{S-1}];
    pos: (B,) global index of tokens[:, 0], -1 for inactive rows;
    max_pos: (B,) optional per-row KV-write cap (see gqa_paged_verify).
    Returns (logits (B, S, V) at every fed position, updated pool)."""
    h = embed_tokens(params, cfg, tokens)
    _, S, _ = h.shape
    pos = jnp.asarray(pos, jnp.int32)
    positions = jnp.maximum(pos, 0)[:, None] + jnp.arange(S)[None, :]
    cos, sin = _cos_sin(cfg, positions)

    def block(lp, h, c):
        x = rmsnorm(lp["attn_norm"], h, cfg.norm_eps)
        o, c = attn.gqa_paged_verify(lp["attn"], cfg, x, cos, sin, c,
                                     block_tables, pos, max_pos)
        h = h + o
        x = rmsnorm(lp["ffn_norm"], h, cfg.norm_eps)
        if cfg.has_moe and "router" in lp["ffn"]:
            y, _ = moe_ffn(lp["ffn"], cfg, x, capacity_factor=moe_cf)
        else:
            y = ffn(lp["ffn"], cfg, x)
        return h + y, c

    new_prefix = []
    for lp, c in zip(params.get("prefix_layers", []), cache.get("prefix", [])):
        h, c = block(lp, h, c)
        new_prefix.append(c)

    def scan_body(h, xs):
        lp, c = xs
        h, c = block(lp, h, c)
        return h, c

    h, new_stack = jax.lax.scan(scan_body, h, (params["layers"], cache["stack"]))
    h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    new_cache = {"stack": new_stack}
    if new_prefix:
        new_cache["prefix"] = new_prefix
    return unembed(params, cfg, h), new_cache


def lm_dense_verify(
    params: Params, cfg: ModelConfig, tokens: jnp.ndarray, cache: Params,
    pos, *, moe_cf=None,
) -> Tuple[jnp.ndarray, Params]:
    """Batched S-token verify step against the dense per-slot cache —
    same contract as ``lm_paged_verify`` without block tables."""
    h = embed_tokens(params, cfg, tokens)
    _, S, _ = h.shape
    pos = jnp.asarray(pos, jnp.int32)
    positions = jnp.maximum(pos, 0)[:, None] + jnp.arange(S)[None, :]
    cos, sin = _cos_sin(cfg, positions)

    def block(lp, h, c):
        x = rmsnorm(lp["attn_norm"], h, cfg.norm_eps)
        o, c = attn.gqa_dense_verify(lp["attn"], cfg, x, cos, sin, c, pos)
        h = h + o
        x = rmsnorm(lp["ffn_norm"], h, cfg.norm_eps)
        if cfg.has_moe and "router" in lp["ffn"]:
            y, _ = moe_ffn(lp["ffn"], cfg, x, capacity_factor=moe_cf)
        else:
            y = ffn(lp["ffn"], cfg, x)
        return h + y, c

    new_prefix = []
    for lp, c in zip(params.get("prefix_layers", []), cache.get("prefix", [])):
        h, c = block(lp, h, c)
        new_prefix.append(c)

    def scan_body(h, xs):
        lp, c = xs
        h, c = block(lp, h, c)
        return h, c

    h, new_stack = jax.lax.scan(scan_body, h, (params["layers"], cache["stack"]))
    h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    new_cache = {"stack": new_stack}
    if new_prefix:
        new_cache["prefix"] = new_prefix
    return unembed(params, cfg, h), new_cache


def init_paged_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                     dtype=None) -> Params:
    """Global KV block pool: every leaf is (num_blocks, Hkv, block_size,
    D or 1) — head-major, the layout the Pallas paged kernels tile — one
    population of blocks shared by all sequences on the engine, leased
    out through serving/kvpool.py block tables."""
    assert supports_paged(cfg), f"{cfg.name}: no paged cache for this family"
    dtype = dtype or _adtype(cfg)
    n_prefix = cfg.first_dense_layers if cfg.has_moe else 0
    n_stack = cfg.num_layers - n_prefix

    if cfg.kv_cache_dtype == "int8":
        def one(lead=()):
            kv_shape = lead + (num_blocks, cfg.num_kv_heads, block_size,
                               cfg.head_dim)
            sc_shape = lead + (num_blocks, cfg.num_kv_heads, block_size, 1)
            return {
                "k": jnp.zeros(kv_shape, jnp.int8),
                "k_scale": jnp.zeros(sc_shape, jnp.float32),
                "v": jnp.zeros(kv_shape, jnp.int8),
                "v_scale": jnp.zeros(sc_shape, jnp.float32),
            }
    else:
        def one(lead=()):
            shape = lead + (num_blocks, cfg.num_kv_heads, block_size,
                            cfg.head_dim)
            return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    cache: Params = {"stack": one(lead=(n_stack,))}
    if n_prefix:
        cache["prefix"] = [one() for _ in range(n_prefix)]
    return cache


def copy_paged_block(cache: Params, src, dst) -> Params:
    """Copy-on-write helper: duplicate block ``src`` into ``dst`` across
    every layer and leaf of the pool (a shared prefix block a request
    must append into is copied first; see kvpool.RadixPrefixCache)."""
    def cp(arr):
        axis = arr.ndim - 4          # block axis: (..., NB, H, BS, D/1)
        blk = jax.lax.dynamic_index_in_dim(arr, src, axis=axis)
        return jax.lax.dynamic_update_index_in_dim(arr, blk, dst, axis=axis)

    return jax.tree_util.tree_map(cp, cache)


# ---------------------------------------------------------------------------
# cache construction (also used by the dry-run via jax.eval_shape)


def init_lm_cache(cfg: ModelConfig, batch: int, cache_len: int,
                  dtype=None) -> Params:
    dtype = dtype or _adtype(cfg)
    eff = cache_len if cfg.sliding_window is None else min(cfg.sliding_window, cache_len)
    n_prefix = cfg.first_dense_layers if cfg.has_moe else 0
    n_stack = cfg.num_layers - n_prefix

    if cfg.attention_type == "mla":
        def one(lead=()):
            return {
                "ckv": jnp.zeros(lead + (batch, eff, cfg.kv_lora_rank), dtype),
                "krope": jnp.zeros(lead + (batch, eff, cfg.qk_rope_head_dim), dtype),
            }
    elif cfg.kv_cache_dtype == "int8":
        def one(lead=()):
            kv_shape = lead + (batch, eff, cfg.num_kv_heads, cfg.head_dim)
            sc_shape = lead + (batch, eff, cfg.num_kv_heads, 1)
            return {
                "k": jnp.zeros(kv_shape, jnp.int8),
                "k_scale": jnp.zeros(sc_shape, jnp.float32),
                "v": jnp.zeros(kv_shape, jnp.int8),
                "v_scale": jnp.zeros(sc_shape, jnp.float32),
            }
    else:
        def one(lead=()):
            return {
                "k": jnp.zeros(lead + (batch, eff, cfg.num_kv_heads, cfg.head_dim), dtype),
                "v": jnp.zeros(lead + (batch, eff, cfg.num_kv_heads, cfg.head_dim), dtype),
            }

    cache: Params = {"stack": one(lead=(n_stack,))}
    if n_prefix:
        cache["prefix"] = [one() for _ in range(n_prefix)]
    return cache
