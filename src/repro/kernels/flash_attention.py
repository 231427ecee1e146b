"""Pallas TPU kernel: blocked online-softmax (flash) prefill attention.

TPU adaptation (DESIGN.md §6): GPU flash-attention's warp-level tiling maps
to a sequential Pallas grid over (batch, q-head, q-block) with an inner
fori-loop over KV blocks; accumulators (m, l, acc) live in VMEM scratch.
Block shapes are multiples of the (8, 128) VPU / (128, 128) MXU tiles.
GQA is handled in the K/V BlockSpec index maps (head h reads KV head
h // group_size) — no KV replication in HBM.

Supports causal masking and sliding-window (ring-relevant band) masking.
Validated against ``repro.kernels.ref.ref_attention`` in interpret mode.
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


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
               scale: float, causal: bool, window: Optional[int],
               block_q: int, block_k: int, seq_kv: int):
    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale            # (bq, D)
    q_pos = qi * block_q + jax.lax.iota(jnp.int32, block_q)

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    n_kv = seq_kv // block_k

    def body(kj, _):
        k_blk = k_ref[0, 0, pl.ds(kj * block_k, block_k), :].astype(
            jnp.float32)
        v_blk = v_ref[0, 0, pl.ds(kj * block_k, block_k), :].astype(
            jnp.float32)
        s = q @ k_blk.T                                     # (bq, bk)
        k_pos = kj * block_k + jax.lax.iota(jnp.int32, block_k)
        mask = jnp.ones((block_q, block_k), dtype=bool)
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if window is not None:
            mask &= k_pos[None, :] > q_pos[:, None] - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + p @ v_blk
        m_scr[...] = m_new
        return ()

    if causal:
        # skip fully-masked kv blocks past the diagonal
        last = jnp.minimum(n_kv, (qi + 1) * block_q // block_k + 1)
    else:
        last = n_kv
    if window is not None:
        first = jnp.maximum(0, (qi * block_q - window) // block_k)
    else:
        first = 0
    jax.lax.fori_loop(first, last, body, ())

    l = jnp.maximum(l_scr[...], 1e-30)
    o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(
    q: jnp.ndarray,            # (B, Hq, Sq, D)
    k: jnp.ndarray,            # (B, Hkv, Skv, D)
    v: jnp.ndarray,            # (B, Hkv, Skv, D)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    assert Sq % block_q == 0 and Skv % block_k == 0, (Sq, Skv, block_q, block_k)

    grid = (B, Hq, Sq // block_q)
    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, seq_kv=Skv)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, Skv, D), lambda b, h, i: (b, h // G, 0, 0)),
            pl.BlockSpec((1, 1, Skv, D), lambda b, h, i: (b, h // G, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
