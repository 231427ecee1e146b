"""Pallas TPU kernel: single-token GQA decode attention (flash-decoding).

One new query token attends to a long KV cache. The grid walks
(batch, kv-head); the group of query heads sharing a KV head is processed
together as the (G, D) left operand of the MXU matmuls — this keeps the
matmul M-dimension >= 8 even for one token, instead of wasting the MXU on
a single row. KV is streamed block-by-block through VMEM with online
softmax in scratch. Supports both linear caches (valid prefix mask) and
ring-buffer sliding-window caches (all slots < min(valid, S) live —
softmax is order-invariant, so ring order needs no unpermute).

The `latency` serving backend profile uses this kernel; validated against
``ref.ref_decode_attention`` in interpret mode.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _dec_kernel(valid_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                *, scale: float, block_k: int, seq_kv: int, ring: bool):
    q = q_ref[0, 0].astype(jnp.float32) * scale             # (G, D)
    valid = valid_ref[pl.program_id(0)]                     # written entries
    live_max = jnp.minimum(valid, seq_kv) if ring else valid

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    n_blocks = pl.cdiv(live_max, block_k)

    def body(kj, _):
        k_blk = k_ref[0, 0, pl.ds(kj * block_k, block_k), :].astype(
            jnp.float32)
        v_blk = v_ref[0, 0, pl.ds(kj * block_k, block_k), :].astype(
            jnp.float32)
        s = q @ k_blk.T                                     # (G, bk)
        slot = kj * block_k + jax.lax.iota(jnp.int32, block_k)
        s = jnp.where((slot < live_max)[None, :], s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + p @ v_blk
        m_scr[...] = m_new
        return ()

    jax.lax.fori_loop(0, n_blocks, body, ())
    l = jnp.maximum(l_scr[...], 1e-30)
    o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def decode_attention(
    q: jnp.ndarray,            # (B, Hq, D) — one token per sequence
    k_cache: jnp.ndarray,      # (B, Hkv, S, D)
    v_cache: jnp.ndarray,      # (B, Hkv, S, D)
    valid_len: jnp.ndarray,    # (B,) int32 — number of written entries
    *,
    ring: bool = False,
    scale: Optional[float] = None,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    B, Hq, D = q.shape
    Hkv, S = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    block_k = min(block_k, S)
    assert S % block_k == 0, (S, block_k)

    qg = q.reshape(B, Hkv, G, D)
    kernel = functools.partial(_dec_kernel, scale=scale, block_k=block_k,
                               seq_kv=S, ring=ring)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, valid: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, S, D), lambda b, h, valid: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, S, D), lambda b, h, valid: (b, h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, valid: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        interpret=interpret,
    )(valid_len.astype(jnp.int32), qg, k_cache, v_cache)
    return out.reshape(B, Hq, D)
