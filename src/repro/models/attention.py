"""Attention: GQA (full / sliding-window / decode) and MLA (DeepSeek-V2).

Three execution modes per layer:
  * ``train`` / ``prefill`` — chunked online-softmax attention in pure JAX
    (``flash_attention_jnp``): O(q_chunk x kv) live memory so 32k prefill
    lowers without materializing (S x S) scores. The Pallas kernels in
    ``repro.kernels`` implement the same contract for the TPU hot path and
    are validated against these semantics.
  * ``decode`` — one new token against a cache: either a full linear cache
    or a ring-buffer sliding-window cache (keys RoPE'd at write time, so
    ring order is irrelevant to softmax).
  * ``cross`` — encoder-decoder cross attention over precomputed KV.

Caches are per-layer dicts of arrays; the trunk stacks them with a leading
``num_layers`` axis for ``lax.scan``.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import Params, apply_rope, dense_init

NEG_INF = -1e30


def _kernel_dispatch(cache_like: Params) -> Optional[bool]:
    """Paged-attention dispatch decision for the engine hot path (see
    ``kernels.ops`` registry): None — run the jnp reference trunk;
    otherwise the Pallas kernel's ``interpret`` flag (False: Mosaic on
    TPU). int8 KV pools always take the reference trunk — the kernels
    stream raw k/v blocks, not (values, scales) pairs."""
    from repro.kernels import ops
    mode = ops.kernel_mode()
    if mode == "reference" or "k_scale" in cache_like:
        return None
    return mode != "mosaic"


def dyn_write(cache: jnp.ndarray, new: jnp.ndarray, pos) -> jnp.ndarray:
    """Write ``new`` (B, 1, ...) into ``cache`` (B, S, ...) at sequence
    position ``pos`` (scalar, or (B,) for ragged continuous batching)."""
    pos = jnp.asarray(pos, jnp.int32)
    new = new.astype(cache.dtype)
    if pos.ndim == 0:
        start = (0, pos) + (0,) * (cache.ndim - 2)
        return jax.lax.dynamic_update_slice(cache, new, start)

    def one(c, n, p):
        return jax.lax.dynamic_update_slice(c, n, (p,) + (0,) * (c.ndim - 1))

    return jax.vmap(one)(cache, new, pos)


# ---------------------------------------------------------------------------
# init


def init_gqa(cfg: ModelConfig, key, dtype=jnp.float32) -> Params:
    d, hq, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, hq * hd, dtype),
        "wk": dense_init(ks[1], d, hkv * hd, dtype),
        "wv": dense_init(ks[2], d, hkv * hd, dtype),
        "wo": dense_init(ks[3], hq * hd, d, dtype, scale=1.0 / math.sqrt(hq * hd)),
    }
    if cfg.use_qkv_bias:
        p["bq"] = jnp.zeros((hq * hd,), dtype)
        p["bk"] = jnp.zeros((hkv * hd,), dtype)
        p["bv"] = jnp.zeros((hkv * hd,), dtype)
    if cfg.use_attn_out_bias:
        p["bo"] = jnp.zeros((d,), dtype)
    return p


def init_mla(cfg: ModelConfig, key, dtype=jnp.float32) -> Params:
    d, h = cfg.d_model, cfg.num_heads
    qn, qr, vh = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 6)
    q_in = cfg.q_lora_rank or d
    p = {
        "w_dkv": dense_init(ks[2], d, cfg.kv_lora_rank + qr, dtype),
        "kv_norm": {"scale": jnp.ones((cfg.kv_lora_rank,), dtype)},
        "w_uk": dense_init(ks[3], cfg.kv_lora_rank, h * qn, dtype),
        "w_uv": dense_init(ks[4], cfg.kv_lora_rank, h * vh, dtype),
        "wo": dense_init(ks[5], h * vh, d, dtype, scale=1.0 / math.sqrt(h * vh)),
    }
    if cfg.q_lora_rank:
        kq = jax.random.split(ks[0], 2)
        p["w_dq"] = dense_init(kq[0], d, cfg.q_lora_rank, dtype)
        p["q_norm"] = {"scale": jnp.ones((cfg.q_lora_rank,), dtype)}
        p["w_uq"] = dense_init(kq[1], cfg.q_lora_rank, h * (qn + qr), dtype)
    else:
        p["w_uq"] = dense_init(ks[0], d, h * (qn + qr), dtype)
    return p


# ---------------------------------------------------------------------------
# chunked online-softmax attention (pure JAX; mirrors the Pallas kernel)


def flash_attention_jnp(
    q: jnp.ndarray,            # (B, Sq, Hq, D)
    k: jnp.ndarray,            # (B, Skv, Hkv, D)
    v: jnp.ndarray,            # (B, Skv, Hkv, Dv)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_chunk: int = 512,
    q_offset=0,                # global position of q[0] (int or traced scalar)
    scale: Optional[float] = None,
    kv_valid_len: Optional[jnp.ndarray] = None,   # (B,) valid kv prefix
) -> jnp.ndarray:
    B, Sq, Hq, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    q = q.reshape(B, Sq, Hkv, G, D)
    qc = min(q_chunk, Sq)
    n_chunks = (Sq + qc - 1) // qc
    pad = n_chunks * qc - Sq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
    q = q.reshape(B, n_chunks, qc, Hkv, G, D)
    q = jnp.moveaxis(q, 1, 0)  # (n_chunks, B, qc, Hkv, G, D)

    kv_pos = jnp.arange(Skv)

    def chunk_body(carry, inp):
        ci, qi = inp
        q_pos = q_offset + ci * qc + jnp.arange(qc)
        # logits: (B, qc, Hkv, G, Skv)
        logits = jnp.einsum("bqhgd,bkhd->bqhgk", qi.astype(jnp.float32),
                            k.astype(jnp.float32)) * scale
        mask = jnp.ones((qc, Skv), dtype=bool)
        if causal:
            mask &= kv_pos[None, :] <= q_pos[:, None]
        if window is not None:
            mask &= kv_pos[None, :] > q_pos[:, None] - window
        mask_b = mask[None, :, None, None, :]
        if kv_valid_len is not None:
            valid = kv_pos[None, :] < kv_valid_len[:, None]     # (B, Skv)
            mask_b = mask_b & valid[:, None, None, None, :]
        logits = jnp.where(mask_b, logits, NEG_INF)
        out = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bqhgk,bkhd->bqhgd", out, v.astype(jnp.float32))
        return carry, out.astype(q.dtype)

    chunk_body = jax.checkpoint(chunk_body)
    _, outs = jax.lax.scan(chunk_body, None, (jnp.arange(n_chunks), q))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, n_chunks * qc, Hkv, G, Dv)
    if pad:
        out = out[:, :Sq]
    return out.reshape(B, Sq, Hq, Dv)


def decode_attention_jnp(
    q: jnp.ndarray,            # (B, 1, Hq, D)
    k_cache: jnp.ndarray,      # (B, S, Hkv, D)
    v_cache: jnp.ndarray,      # (B, S, Hkv, Dv)
    valid_len: jnp.ndarray,    # scalar or (B,): number of written entries
    *,
    ring: bool = False,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """One-token attention against a cache. ``ring=True`` means the cache is
    a ring buffer (all slots < min(valid_len, S) are live past tokens)."""
    B, S, Hkv, D = k_cache.shape
    Hq = q.shape[2]
    G = Hq // Hkv
    Dv = v_cache.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    qv = q.reshape(B, Hkv, G, D)
    logits = jnp.einsum("bhgd,bkhd->bhgk", qv.astype(jnp.float32),
                        k_cache.astype(jnp.float32)) * scale
    slot = jnp.arange(S)
    vl = jnp.asarray(valid_len)
    if vl.ndim == 0:
        vl = jnp.broadcast_to(vl, (B,))
    cap = jnp.minimum(vl, S) if ring else vl
    live = slot[None, :] < cap[:, None]                     # (B, S)
    logits = jnp.where(live[:, None, None, :], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", w, v_cache.astype(jnp.float32))
    return out.reshape(B, 1, Hq, Dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA layer forward


def _proj_qkv(p: Params, cfg: ModelConfig, x: jnp.ndarray):
    B, S, _ = x.shape
    q = x @ p["wq"].astype(x.dtype)
    k = x @ p["wk"].astype(x.dtype)
    v = x @ p["wv"].astype(x.dtype)
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = q.reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    return q, k, v


def _out_proj(p: Params, cfg: ModelConfig, o: jnp.ndarray):
    B, S = o.shape[:2]
    o = o.reshape(B, S, -1) @ p["wo"].astype(o.dtype)
    if "bo" in p:
        o = o + p["bo"].astype(o.dtype)
    return o


def gqa_full(params: Params, cfg: ModelConfig, x, cos, sin, *,
             causal: bool = True, q_chunk: int = 512) -> jnp.ndarray:
    """Training / encoder forward (no cache)."""
    q, k, v = _proj_qkv(params, cfg, x)
    if cos is not None:
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    o = flash_attention_jnp(q, k, v, causal=causal,
                            window=cfg.sliding_window, q_chunk=q_chunk)
    return _out_proj(params, cfg, o)


def gqa_prefill(params: Params, cfg: ModelConfig, x, cos, sin, cache_len: int,
                q_chunk: int = 512) -> Tuple[jnp.ndarray, Params]:
    """Causal forward that also returns the populated per-layer cache.

    Full cache: (B, cache_len, Hkv, D) zero-padded past S.
    Sliding window: ring layout of the last ``window`` keys (cache_len is
    the window size in that case).
    """
    B, S, _ = x.shape
    q, k, v = _proj_qkv(params, cfg, x)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    o = flash_attention_jnp(q, k, v, causal=True, window=cfg.sliding_window,
                            q_chunk=q_chunk)
    w = cfg.sliding_window
    if w is not None:
        # keep the last `window` tokens, laid out at ring slots pos % window
        last = max(S - w, 0)
        idx_tok = last + jnp.arange(min(w, S))
        ring_slot = idx_tok % w
        kc = jnp.zeros((B, w, cfg.num_kv_heads, cfg.head_dim), k.dtype)
        vc = jnp.zeros_like(kc)
        kc = kc.at[:, ring_slot].set(k[:, idx_tok])
        vc = vc.at[:, ring_slot].set(v[:, idx_tok])
        cache = _pack_kv(cfg, kc, vc)
    else:
        pad = cache_len - S
        kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cache = _pack_kv(cfg, kc, vc)
    return _out_proj(params, cfg, o), cache


def _pack_kv(cfg: ModelConfig, k: jnp.ndarray, v: jnp.ndarray) -> Params:
    """Cache layout: bf16 {k, v} or int8 {k, k_scale, v, v_scale}
    (per-token-per-head absmax; §Perf H1 iteration 3)."""
    if cfg.kv_cache_dtype != "int8":
        return {"k": k, "v": v}
    from repro.serving.kvquant import quantize
    kq, ks = quantize(k)
    vq, vs = quantize(v)
    return {"k": kq, "k_scale": ks, "v": vq, "v_scale": vs}


def _unpack_kv(cfg: ModelConfig, cache: Params):
    if "k_scale" not in cache:
        return cache["k"], cache["v"]
    from repro.serving.kvquant import dequantize
    return (dequantize(cache["k"], cache["k_scale"]),
            dequantize(cache["v"], cache["v_scale"]))


def gqa_decode(params: Params, cfg: ModelConfig, x, cos, sin,
               cache: Params, pos) -> Tuple[jnp.ndarray, Params]:
    """One-token decode. ``pos`` is the global index of the new token
    (scalar int32). Returns (out, updated cache)."""
    B = x.shape[0]
    q, k, v = _proj_qkv(params, cfg, x)           # S == 1
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    w = cfg.sliding_window
    ring = w is not None
    slot = (jnp.asarray(pos) % w) if ring else pos
    if "k_scale" in cache:
        from repro.serving.kvquant import quantize
        kq, ks = quantize(k)
        vq, vs = quantize(v)
        new_cache = {"k": dyn_write(cache["k"], kq, slot),
                     "k_scale": dyn_write(cache["k_scale"], ks, slot),
                     "v": dyn_write(cache["v"], vq, slot),
                     "v_scale": dyn_write(cache["v_scale"], vs, slot)}
    else:
        new_cache = {"k": dyn_write(cache["k"], k, slot),
                     "v": dyn_write(cache["v"], v, slot)}
    kc, vc = _unpack_kv(cfg, new_cache)
    o = decode_attention_jnp(q, kc, vc, jnp.asarray(pos) + 1, ring=ring)
    return _out_proj(params, cfg, o), new_cache


# ---------------------------------------------------------------------------
# paged GQA (block-pool KV cache; serving/kvpool.py owns the block ids)
#
# The cache is a GLOBAL pool of KV blocks shaped (num_blocks, Hkv,
# block_size, D) shared by every sequence on the engine; a sequence's KV
# for token position p lives at pool[table[p // bs], :, p % bs]. The pool
# is head-major because the Pallas kernels (kernels/paged_attention.py)
# read one (block_size, D) tile per KV head, and Mosaic accepts a tile
# only as the array's trailing dims. These jnp paths define the
# semantics those kernels implement for the TPU hot path: they gather the
# leased blocks into token order and reuse the dense attention math, so a
# paged engine is arithmetically identical to the dense one.


def _paged_geometry(pool: Params):
    """(num_blocks, block_size) of a pool whose leaves are
    (..., NB, Hkv, BS, D)."""
    k = pool["stack"]["k"] if "stack" in pool else pool["k"]
    return k.shape[-4], k.shape[-2]


def _paged_write(pool: Params, k: jnp.ndarray, v: jnp.ndarray,
                 blk: jnp.ndarray, off: jnp.ndarray) -> Params:
    """Scatter new tokens into the pool. ``k``/``v``: (N, Hkv, D);
    ``blk``/``off``: (N,) pool block id and offset inside the block
    (``blk`` >= num_blocks is dropped — padded/inactive writes)."""
    if "k_scale" in pool:
        from repro.serving.kvquant import quantize
        kq, ks = quantize(k)
        vq, vs = quantize(v)
        new = {"k": kq, "k_scale": ks, "v": vq, "v_scale": vs}
    else:
        new = {"k": k, "v": v}
    # two advanced indices split by a slice: the indexed view is
    # (N, Hkv, D), the layout of the new tokens
    return {name: arr.at[blk, :, off].set(new[name].astype(arr.dtype),
                                          mode="drop")
            for name, arr in pool.items()}


def _paged_gather(cfg: ModelConfig, pool: Params, blk: jnp.ndarray,
                  off: jnp.ndarray):
    """Read tokens back out of the pool in sequence order.
    ``blk``/``off``: (..., S) block ids and in-block offsets ->
    (kc, vc) (..., S, Hkv, D)."""
    gathered = {name: arr[blk, :, off] for name, arr in pool.items()}
    return _unpack_kv(cfg, gathered)


def paged_gather_ctx(cache: Params, table_ctx: jnp.ndarray) -> Params:
    """Lease-read the context blocks of one sequence out of the pool:
    every leaf (..., NB, H, BS, D) -> (..., ctx*BS, H, D) in token order.
    A pure read — the pool buffer is never rewritten (that is the whole
    reason prefill splits into gather / compute / scatter)."""
    def take(leaf):
        ax = leaf.ndim - 4                        # block axis
        g = jnp.swapaxes(jnp.take(leaf, table_ctx, axis=ax), ax + 1, ax + 2)
        shp = g.shape                             # (..., ctx, BS, H, D)
        return g.reshape(shp[:ax] + (shp[ax] * shp[ax + 1],) + shp[ax + 2:])

    return jax.tree_util.tree_map(take, cache)


def paged_scatter(cache: Params, new_kv: Params, block_table: jnp.ndarray,
                  start, s_real) -> Params:
    """Write a request's freshly-computed suffix KV into its pool blocks
    (positions ``start .. start+s_real-1`` through ``block_table``).
    Compiled with the pool donated: the update aliases in place, costing
    O(suffix), not O(pool). Leaves pair as (..., NB, H, BS, D) with
    (..., Sb, H, D)."""
    k0 = new_kv["stack"]["k"] if "stack" in new_kv else new_kv["k"]
    Sb = k0.shape[-3]
    nb, bs = _paged_geometry(cache)
    pos = start + jnp.arange(Sb)
    blk = block_table[jnp.clip(pos // bs, 0, block_table.shape[0] - 1)]
    blk = jnp.where(jnp.arange(Sb) < s_real, blk, nb)          # drop pads
    off = pos % bs

    def put(leaf, upd):
        upd = upd.astype(leaf.dtype)
        if leaf.ndim == 5:                        # stacked layers leading
            # the split advanced indices lead the indexed view: (Sb, L, H, D)
            return leaf.at[:, blk, :, off].set(jnp.moveaxis(upd, 1, 0),
                                               mode="drop")
        return leaf.at[blk, :, off].set(upd, mode="drop")

    return jax.tree_util.tree_map(put, cache, new_kv)


def gqa_paged_prefill(params: Params, cfg: ModelConfig, x, cos, sin,
                      ctx_kv: Params, start, s_real
                      ) -> Tuple[jnp.ndarray, Params]:
    """Suffix prefill of one layer against gathered context KV.

    ``x``: (1, Sb, d) — the UNCACHED tail of the prompt, right-padded to
    a bucket; ``ctx_kv``: this layer's pool blocks gathered in token
    order (``paged_gather_ctx``), entries >= ``start`` masked out;
    ``s_real`` <= Sb is the count of live (non-pad) suffix tokens.
    Queries run at global offset ``start`` so causality and RoPE line up
    with the cached prefix. Returns (out, packed suffix KV for
    ``paged_scatter``) — the pool itself is untouched here.

    Kernel dispatch: under ``mosaic``/``interpret`` the chunk attends
    through ``kernels.paged_prefill_attention`` — the gathered context
    is presented head-major as ONE pool block (the kernel's block-table
    contract covers any block size), the chunk's fresh KV rides as
    head-major operands, and one online softmax streams context + self
    causally. The jnp math below is the ``reference`` trunk the kernel
    is validated against."""
    B, Sb, _ = x.shape
    q, k, v = _proj_qkv(params, cfg, x)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    kc, vc = _unpack_kv(cfg, ctx_kv)              # (CtxT, Hkv, D)
    CtxT = kc.shape[0]
    interpret = _kernel_dispatch(ctx_kv)
    if interpret is not None:
        from repro.kernels import ops
        o = ops.paged_prefill_attention(
            q[0], jnp.swapaxes(kc, 0, 1)[None], jnp.swapaxes(vc, 0, 1)[None],
            jnp.swapaxes(k[0], 0, 1), jnp.swapaxes(v[0], 0, 1),
            jnp.zeros((1,), jnp.int32), start, s_real,
            interpret=interpret)[None]
        return _out_proj(params, cfg, o.astype(x.dtype)), _pack_kv(cfg, k[0], v[0])
    Hkv, G = cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads
    scale = 1.0 / math.sqrt(cfg.head_dim)
    qg = q.reshape(B, Sb, Hkv, G, cfg.head_dim).astype(jnp.float32)
    kfull = jnp.concatenate([kc[None].astype(jnp.float32),
                             k.astype(jnp.float32)], axis=1)   # (1, K, H, D)
    vfull = jnp.concatenate([vc[None].astype(jnp.float32),
                             v.astype(jnp.float32)], axis=1)
    logits = jnp.einsum("bqhgd,bkhd->bqhgk", qg, kfull) * scale
    i = jnp.arange(Sb)
    live_ctx = jnp.broadcast_to((jnp.arange(CtxT) < start)[None, :],
                                (Sb, CtxT))
    live_new = (i[None, :] <= i[:, None]) & (i[None, :] < s_real)
    mask = jnp.concatenate([live_ctx, live_new], axis=1)       # (Sb, K)
    logits = jnp.where(mask[None, :, None, None, :], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bqhgk,bkhd->bqhgd", w, vfull)
    o = o.reshape(B, Sb, cfg.num_heads, cfg.head_dim).astype(x.dtype)
    return _out_proj(params, cfg, o), _pack_kv(cfg, k[0], v[0])


def gqa_paged_decode(params: Params, cfg: ModelConfig, x, cos, sin,
                     pool: Params, block_tables: jnp.ndarray, pos
                     ) -> Tuple[jnp.ndarray, Params]:
    """One-token decode against a paged cache. ``block_tables``:
    (B, NBseq) pool block ids; ``pos``: (B,) global index of the new
    token, or -1 for inactive batch slots (their write is dropped and
    their output is garbage the engine ignores).

    Kernel dispatch: under ``mosaic``/``interpret`` the attention runs
    through ``kernels.paged_decode_attention`` directly against the pool
    — each sequence's blocks are streamed through its scalar-prefetched
    table, with NO gathered (B, Smax) KV copy materialized per step (the
    reference trunk's gather exists to reuse the dense math, not because
    the contract needs it). Inactive rows carry valid_len 0 — every
    block is skipped and the flushed output is the garbage the engine
    ignores."""
    B = x.shape[0]
    q, k, v = _proj_qkv(params, cfg, x)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    nb, bs = _paged_geometry(pool)
    pos = jnp.asarray(pos, jnp.int32)
    safe = jnp.maximum(pos, 0)
    blk = jnp.take_along_axis(block_tables, (safe // bs)[:, None], axis=1)[:, 0]
    blk = jnp.where(pos >= 0, blk, nb)                     # drop inactive
    pool = _paged_write(pool, k[:, 0], v[:, 0], blk, safe % bs)
    interpret = _kernel_dispatch(pool)
    if interpret is not None:
        from repro.kernels import ops
        o = ops.paged_decode_attention(q[:, 0], pool["k"], pool["v"],
                                       block_tables, pos + 1,
                                       interpret=interpret)[:, None]
        return _out_proj(params, cfg, o.astype(x.dtype)), pool
    t = jnp.arange(block_tables.shape[1] * bs)
    kc, vc = _paged_gather(cfg, pool, jnp.take(block_tables, t // bs, axis=1),
                           t % bs)                         # (B, Smax, ...)
    o = decode_attention_jnp(q, kc, vc, pos + 1)
    return _out_proj(params, cfg, o), pool


def gqa_paged_verify(params: Params, cfg: ModelConfig, x, cos, sin,
                     pool: Params, block_tables: jnp.ndarray, pos,
                     max_pos=None) -> Tuple[jnp.ndarray, Params]:
    """S-token speculative verify step of one layer against the block
    pool — the batched sibling of ``gqa_paged_decode``: every row feeds
    ``S`` consecutive tokens (its last sampled token plus S-1 drafted
    ones) at positions ``pos .. pos+S-1``, writes their KV through its
    block table, and attends causally over the full cached sequence.

    ``pos``: (B,) global index of ``x[:, 0]``, -1 for inactive rows
    (writes dropped, output garbage the engine masks). ``max_pos``:
    (B,) last position each row may legitimately write — a fed span can
    extend past a row's LEASED blocks (the table's zero padding would
    alias block 0, clobbering another request's KV), so writes beyond
    it are dropped; the engine's on-device max_new/room masks stop
    emission before those positions matter. Stale pool entries past a
    row's cursor are rewritten by this chunk before the gather, so the
    attention only ever sees valid KV."""
    B, S, _ = x.shape
    q, k, v = _proj_qkv(params, cfg, x)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    nb, bs = _paged_geometry(pool)
    nbseq = block_tables.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    p = jnp.maximum(pos, 0)[:, None] + jnp.arange(S)[None, :]      # (B, S)
    blk = jnp.take_along_axis(block_tables,
                              jnp.clip(p // bs, 0, nbseq - 1), axis=1)
    ok = (pos[:, None] >= 0) & (p < nbseq * bs)
    if max_pos is not None:
        ok = ok & (p <= jnp.asarray(max_pos, jnp.int32)[:, None])
    blk = jnp.where(ok, blk, nb)                                    # drop
    pool = _paged_write(pool, k.reshape(B * S, cfg.num_kv_heads,
                                        cfg.head_dim),
                        v.reshape(B * S, cfg.num_kv_heads, cfg.head_dim),
                        blk.reshape(B * S), (p % bs).reshape(B * S))
    t = jnp.arange(nbseq * bs)
    kc, vc = _paged_gather(cfg, pool, jnp.take(block_tables, t // bs, axis=1),
                           t % bs)                         # (B, Smax, ...)
    Hkv, G = cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads
    scale = 1.0 / math.sqrt(cfg.head_dim)
    qg = q.reshape(B, S, Hkv, G, cfg.head_dim).astype(jnp.float32)
    logits = jnp.einsum("bqhgd,bkhd->bqhgk", qg,
                        kc.astype(jnp.float32)) * scale
    live = jnp.arange(nbseq * bs)[None, None, :] <= p[:, :, None]  # (B, S, K)
    logits = jnp.where(live[:, :, None, None, :], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bqhgk,bkhd->bqhgd", w, vc.astype(jnp.float32))
    o = o.reshape(B, S, cfg.num_heads, cfg.head_dim).astype(x.dtype)
    return _out_proj(params, cfg, o), pool


def gqa_dense_verify(params: Params, cfg: ModelConfig, x, cos, sin,
                     cache: Params, pos) -> Tuple[jnp.ndarray, Params]:
    """S-token speculative verify step of one layer against a dense
    (B, Smax) per-slot cache — same contract as ``gqa_paged_verify``
    with slot rows instead of block tables (``pos`` -1 = inactive,
    writes dropped)."""
    B, S, _ = x.shape
    q, k, v = _proj_qkv(params, cfg, x)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    Smax = cache["k"].shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    p = jnp.maximum(pos, 0)[:, None] + jnp.arange(S)[None, :]      # (B, S)
    pw = jnp.where((pos[:, None] >= 0) & (p < Smax), p, Smax)      # drop
    bi = jnp.arange(B)[:, None]
    if "k_scale" in cache:
        from repro.serving.kvquant import quantize
        kq, ks = quantize(k)
        vq, vs = quantize(v)
        new_cache = {
            "k": cache["k"].at[bi, pw].set(kq.astype(cache["k"].dtype),
                                           mode="drop"),
            "k_scale": cache["k_scale"].at[bi, pw].set(ks, mode="drop"),
            "v": cache["v"].at[bi, pw].set(vq.astype(cache["v"].dtype),
                                           mode="drop"),
            "v_scale": cache["v_scale"].at[bi, pw].set(vs, mode="drop")}
    else:
        new_cache = {
            "k": cache["k"].at[bi, pw].set(k.astype(cache["k"].dtype),
                                           mode="drop"),
            "v": cache["v"].at[bi, pw].set(v.astype(cache["v"].dtype),
                                           mode="drop")}
    kc, vc = _unpack_kv(cfg, new_cache)                            # (B, Smax)
    Hkv, G = cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads
    scale = 1.0 / math.sqrt(cfg.head_dim)
    qg = q.reshape(B, S, Hkv, G, cfg.head_dim).astype(jnp.float32)
    logits = jnp.einsum("bqhgd,bkhd->bqhgk", qg,
                        kc.astype(jnp.float32)) * scale
    live = jnp.arange(Smax)[None, None, :] <= p[:, :, None]
    logits = jnp.where(live[:, :, None, None, :], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bqhgk,bkhd->bqhgd", w, vc.astype(jnp.float32))
    o = o.reshape(B, S, cfg.num_heads, cfg.head_dim).astype(x.dtype)
    return _out_proj(params, cfg, o), new_cache


# ---------------------------------------------------------------------------
# dense per-slot chunk append (continuous batching on the DENSE cache)
#
# The chunked-prefill trunk (lm_chunk_prefill == lm_paged_prefill) is
# layout-agnostic: it only needs "this sequence's cached KV in token
# order" as ctx_kv. The paged engine gathers that through a block table
# (paged_gather_ctx); the dense engine gathers one slot's rows out of its
# (B, S, ...) cache with these two helpers, so BOTH cache disciplines
# share one chunk-append code path — and one equivalence contract.


def _slot_axis(path) -> int:
    """Batch/slot axis of a dense-cache leaf: prefix-layer leaves are
    (B, S, ...), stacked-layer leaves are (L, B, S, ...)."""
    return 0 if any(getattr(k, "key", None) == "prefix" for k in path) else 1


def dense_gather_slot(cache: Params, slot) -> Params:
    """Read ONE slot's rows out of the dense cache: every leaf
    (..., B, S, H, D) -> (..., S, H, D) in token order. The result is the
    ``ctx_kv`` of a chunk prefill (entries >= ``start`` are masked by the
    compute, so stale rows past the cursor are harmless)."""
    def take(path, leaf):
        return jax.lax.dynamic_index_in_dim(leaf, slot, axis=_slot_axis(path),
                                            keepdims=False)
    return jax.tree_util.tree_map_with_path(take, cache)


def dense_scatter_slot(cache: Params, new_kv: Params, slot, start,
                       s_real) -> Params:
    """Write a chunk's fresh KV into one slot's rows at positions
    ``start .. start+s_real-1`` (bucket pads dropped). Compiled with the
    cache donated — an in-place O(chunk) update, not an O(cache) rebuild
    like admission's whole-row insert."""
    k0 = new_kv["stack"]["k"] if "stack" in new_kv else new_kv["k"]
    Sb = k0.shape[-3]

    def put(path, leaf, upd):
        S = leaf.shape[-3]
        pos = start + jnp.arange(Sb)
        pos = jnp.where(jnp.arange(Sb) < s_real, pos, S)       # drop pads
        upd = upd.astype(leaf.dtype)
        if _slot_axis(path) == 0:                   # (B, S, H, D)
            return leaf.at[slot, pos].set(upd, mode="drop")
        return leaf.at[:, slot, pos].set(upd, mode="drop")     # (L, B, S, ...)

    return jax.tree_util.tree_map_with_path(put, cache, new_kv)


def cross_kv(params: Params, cfg: ModelConfig, enc_out: jnp.ndarray):
    B, S, _ = enc_out.shape
    k = (enc_out @ params["wk"].astype(enc_out.dtype)).reshape(
        B, S, cfg.num_kv_heads, cfg.head_dim)
    v = (enc_out @ params["wv"].astype(enc_out.dtype)).reshape(
        B, S, cfg.num_kv_heads, cfg.head_dim)
    return {"k": k, "v": v}


def cross_attend(params: Params, cfg: ModelConfig, x, kv: Params,
                 q_chunk: int = 512) -> jnp.ndarray:
    B, S, _ = x.shape
    q = (x @ params["wq"].astype(x.dtype)).reshape(B, S, cfg.num_heads, cfg.head_dim)
    o = flash_attention_jnp(q, kv["k"], kv["v"], causal=False, q_chunk=q_chunk)
    return _out_proj(params, cfg, o)


# ---------------------------------------------------------------------------
# MLA (multi-head latent attention) — DeepSeek-V2


def _mla_q(params: Params, cfg: ModelConfig, x):
    B, S, _ = x.shape
    h = cfg.num_heads
    if cfg.q_lora_rank:
        cq = x @ params["w_dq"].astype(x.dtype)
        from repro.models.common import rmsnorm
        cq = rmsnorm(params["q_norm"], cq, cfg.norm_eps)
        q = cq @ params["w_uq"].astype(x.dtype)
    else:
        q = x @ params["w_uq"].astype(x.dtype)
    q = q.reshape(B, S, h, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    return q[..., : cfg.qk_nope_head_dim], q[..., cfg.qk_nope_head_dim:]


def _mla_latent(params: Params, cfg: ModelConfig, x, cos, sin):
    """Compress x into the latent KV stream: c_kv (B,S,r), k_rope (B,S,dr)."""
    from repro.models.common import rmsnorm
    ckv = x @ params["w_dkv"].astype(x.dtype)
    c, k_rope = ckv[..., : cfg.kv_lora_rank], ckv[..., cfg.kv_lora_rank:]
    c = rmsnorm(params["kv_norm"], c, cfg.norm_eps)
    # k_rope is a single shared rotary key stream (one "head")
    k_rope = apply_rope(k_rope[..., None, :], cos, sin)[..., 0, :]
    return c, k_rope


def mla_full(params: Params, cfg: ModelConfig, x, cos, sin, *,
             q_chunk: int = 512) -> jnp.ndarray:
    """Train/prefill MLA via naive expansion (cache-free)."""
    B, S, _ = x.shape
    h = cfg.num_heads
    qn, qr, vh = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    q_nope, q_rope = _mla_q(params, cfg, x)
    q_rope = apply_rope(q_rope, cos, sin)
    c, k_rope = _mla_latent(params, cfg, x, cos, sin)
    k_nope = (c @ params["w_uk"].astype(x.dtype)).reshape(B, S, h, qn)
    v = (c @ params["w_uv"].astype(x.dtype)).reshape(B, S, h, vh)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                                  (B, S, h, qr))], axis=-1)
    scale = 1.0 / math.sqrt(qn + qr)
    o = flash_attention_jnp(q, k, v, causal=True, q_chunk=q_chunk, scale=scale)
    return o.reshape(B, S, -1) @ params["wo"].astype(x.dtype)


def mla_prefill(params: Params, cfg: ModelConfig, x, cos, sin, cache_len: int,
                q_chunk: int = 512) -> Tuple[jnp.ndarray, Params]:
    B, S, _ = x.shape
    out = mla_full(params, cfg, x, cos, sin, q_chunk=q_chunk)
    c, k_rope = _mla_latent(params, cfg, x, cos, sin)
    pad = cache_len - S
    cache = {
        "ckv": jnp.pad(c, ((0, 0), (0, pad), (0, 0))),
        "krope": jnp.pad(k_rope, ((0, 0), (0, pad), (0, 0))),
    }
    return out, cache


def mla_decode(params: Params, cfg: ModelConfig, x, cos, sin,
               cache: Params, pos) -> Tuple[jnp.ndarray, Params]:
    """Absorbed-matrices MLA decode: attention runs in the latent space, so
    the cache is (kv_lora + rope_dim) per token instead of 2*H*D — the MLA
    serving advantage."""
    B = x.shape[0]
    h = cfg.num_heads
    qn, qr, vh = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    q_nope, q_rope = _mla_q(params, cfg, x)            # (B,1,h,qn),(B,1,h,qr)
    q_rope = apply_rope(q_rope, cos, sin)
    c_new, krope_new = _mla_latent(params, cfg, x, cos, sin)
    ckv = dyn_write(cache["ckv"], c_new, pos)
    krope = dyn_write(cache["krope"], krope_new, pos)

    # absorb W_uk into q: q_lat (B,h,r)
    w_uk = params["w_uk"].astype(x.dtype).reshape(r, h, qn)
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], w_uk)
    logits = jnp.einsum("bhr,bsr->bhs", q_lat.astype(jnp.float32),
                        ckv.astype(jnp.float32))
    logits += jnp.einsum("bhd,bsd->bhs", q_rope[:, 0].astype(jnp.float32),
                         krope.astype(jnp.float32))
    logits *= 1.0 / math.sqrt(qn + qr)
    S = ckv.shape[1]
    posb = jnp.broadcast_to(jnp.asarray(pos), (B,))
    live = jnp.arange(S)[None, None, :] <= posb[:, None, None]
    logits = jnp.where(live, logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    o_lat = jnp.einsum("bhs,bsr->bhr", w, ckv.astype(jnp.float32)).astype(x.dtype)
    # absorb W_uv on the way out
    w_uv = params["w_uv"].astype(x.dtype).reshape(r, h, vh)
    o = jnp.einsum("bhr,rhv->bhv", o_lat, w_uv).reshape(B, 1, h * vh)
    out = o @ params["wo"].astype(x.dtype)
    return out, {"ckv": ckv, "krope": krope}
