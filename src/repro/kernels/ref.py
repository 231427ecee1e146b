"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def ref_attention(q, k, v, *, causal=True, window: Optional[int] = None,
                  scale: Optional[float] = None):
    """q: (B,Hq,Sq,D); k/v: (B,Hkv,Skv,D). Naive full-materialized softmax."""
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    k = jnp.repeat(k, G, axis=1)
    v = jnp.repeat(v, G, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    qi = jnp.arange(Sq)[:, None]
    kj = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= kj <= qi
    if window is not None:
        mask &= kj > qi - window
    s = jnp.where(mask[None, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v.astype(jnp.float32)).astype(q.dtype)


def ref_decode_attention(q, k_cache, v_cache, valid_len, *, ring=False,
                         scale: Optional[float] = None):
    """q: (B,Hq,D); caches: (B,Hkv,S,D); valid_len: (B,)."""
    B, Hq, D = q.shape
    Hkv, S = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    k = jnp.repeat(k_cache, G, axis=1)
    v = jnp.repeat(v_cache, G, axis=1)
    s = jnp.einsum("bhd,bhkd->bhk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    slot = jnp.arange(S)[None, :]
    vl = valid_len[:, None]
    live = slot < jnp.minimum(vl, S) if ring else slot < vl
    s = jnp.where(live[:, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhk,bhkd->bhd", w, v.astype(jnp.float32)).astype(q.dtype)


def ref_paged_decode_attention(q, k_pool, v_pool, block_tables, valid_len,
                               *, scale: Optional[float] = None):
    """Paged decode oracle: gather KV through the block table, then run the
    dense decode reference.

    q: (B, Hq, D); pools: (NB, Hkv, BS, D); block_tables: (B, NBseq) int32
    ids into the pool's leading axis; valid_len: (B,) written tokens."""
    B = q.shape[0]
    Hkv = k_pool.shape[1]
    # (B, NBseq, Hkv, BS, D) -> (B, Hkv, NBseq*BS, D)
    def gather(pool):
        g = jnp.moveaxis(jnp.take(pool, block_tables, axis=0), 2, 1)
        return g.reshape(B, Hkv, -1, pool.shape[-1])

    return ref_decode_attention(q, gather(k_pool), gather(v_pool), valid_len,
                                ring=False, scale=scale)


def ref_paged_prefill_attention(q, k_pool, v_pool, k_new, v_new,
                                block_table, start, s_real,
                                *, scale: Optional[float] = None):
    """Chunked prefill-append oracle: one sequence's query chunk attends
    the KV it already cached (gathered through the block table, positions
    ``< start``) PLUS the chunk's own fresh KV (causal within the chunk,
    limited to ``s_real`` live tokens — the rest is bucket padding).

    q: (Sb, Hq, D) chunk queries at global offset ``start``;
    pools: (NB, Hkv, BS, D); k_new/v_new: (Hkv, Sb, D); block_table:
    (NBctx,) int32. Returns (Sb, Hq, Dv)."""
    Sb, Hq, D = q.shape
    Hkv = k_pool.shape[1]
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    def tokens(pool, new):          # context blocks + chunk, token-major
        ctx = jnp.moveaxis(jnp.take(pool, block_table, axis=0), 2, 1)
        ctx = ctx.reshape(-1, Hkv, pool.shape[-1])         # (CtxT, Hkv, D)
        return ctx, jnp.concatenate([ctx, jnp.moveaxis(new, 0, 1)], axis=0)

    ctx_k, k = tokens(k_pool, k_new)                     # (CtxT+Sb, Hkv, D)
    _, v = tokens(v_pool, v_new)
    CtxT = ctx_k.shape[0]
    k = jnp.repeat(k, G, axis=1)                        # (K, Hq, D)
    v = jnp.repeat(v, G, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale       # (Hq, Sb, K)
    qi = jnp.arange(Sb)[:, None]
    live_ctx = jnp.broadcast_to((jnp.arange(CtxT) < start)[None, :],
                                (Sb, CtxT))
    kj = jnp.arange(Sb)[None, :]
    live_new = (kj <= qi) & (kj < s_real)
    mask = jnp.concatenate([live_ctx, live_new], axis=1)      # (Sb, K)
    s = jnp.where(mask[None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("hqk,khd->qhd", w, v.astype(jnp.float32))
    return out.astype(q.dtype)


def ref_ssd(x, dt, A, Bm, Cm):
    """Naive O(L) recurrence. x: (B,L,H,P); dt: (B,L,H); A: (H,);
    Bm/Cm: (B,L,H,N). Returns (y (B,L,H,P) f32, final_state (B,H,P,N) f32)."""
    B, L, H, P = x.shape
    N = Bm.shape[-1]
    f32 = jnp.float32
    x, dt, A, Bm, Cm = (t.astype(f32) for t in (x, dt, A, Bm, Cm))

    def step(h, inp):
        xt, dtt, bt, ct = inp            # (B,H,P), (B,H), (B,H,N), (B,H,N)
        g = jnp.exp(dtt * A[None, :])
        h = h * g[..., None, None] + jnp.einsum("bhp,bhn->bhpn",
                                                xt * dtt[..., None], bt)
        y = jnp.einsum("bhpn,bhn->bhp", h, ct)
        return h, y

    h0 = jnp.zeros((B, H, P, N), f32)
    xs = (jnp.moveaxis(x, 1, 0), jnp.moveaxis(dt, 1, 0),
          jnp.moveaxis(Bm, 1, 0), jnp.moveaxis(Cm, 1, 0))
    final, ys = jax.lax.scan(step, h0, xs)
    return jnp.moveaxis(ys, 0, 1), final
