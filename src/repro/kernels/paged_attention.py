"""Pallas TPU kernels: GQA attention over a PAGED cache.

Two kernels share the block-table discipline:

  * ``paged_decode_attention`` — one new token per sequence against the
    cached blocks (the decode hot path);
  * ``paged_prefill_attention`` — a PREFILL CHUNK: ``Sb`` queries of one
    sequence attend every token the sequence already cached (streamed
    block by block through its table, positions ``< start``) plus the
    chunk's own fresh KV, causal within the chunk. This is the
    chunk-append contract continuous batching needs: a long prompt is
    prefilled ``chunk_tokens`` at a time across engine steps, each chunk
    attending cached-prefix + itself, so decode iterations interleave
    between chunks instead of stalling behind a whole-prompt prefill.


Decode: same online-softmax structure as ``decode_attention.py``, but KV lives in
a global block pool shaped (num_blocks, Hkv, block_size, D) shared by
every sequence, and each sequence names its blocks through a block table.
The pool is head-major so that one kernel block — one KV head of one
pool block, ``(block_size, D)`` — is the trailing two dims of the array:
Mosaic requires a block's last two dims to be (8, 128)-divisible or
equal to the array's, and a ``(.., 1, D)`` slice of a token-major
``(.., block_size, Hkv, D)`` pool is neither.
The grid walks (batch, kv-head, block-slot); the per-sequence block table
is a scalar-prefetch operand, so each KV block's index map dereferences
``table[b, j]`` and the DMA engine streams exactly the blocks the
sequence owns — attention never touches another request's memory, and a
shared prefix block is read in place by every sequence that leases it
(no gather materialization, no copies).

Scratch accumulators (m, l, acc) persist across the sequential block-slot
grid dimension; the output tile is flushed once on the last slot. Blocks
past ``valid_len`` are skipped entirely (their DMA still points at a
real block, masked out of the softmax). Validated against
``ref.ref_paged_decode_attention`` in interpret mode.
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


def _paged_kernel(bt_ref, valid_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale: float, block_size: int,
                  blocks_per_seq: int):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    valid = valid_ref[b]                                    # written tokens
    start = j * block_size

    @pl.when(start < valid)
    def _attend():
        q = q_ref[0, 0].astype(jnp.float32) * scale         # (G, D)
        k_blk = k_ref[0, 0].astype(jnp.float32)             # (bs, D)
        v_blk = v_ref[0, 0].astype(jnp.float32)
        s = q @ k_blk.T                                     # (G, bs)
        slot = start + jax.lax.iota(jnp.int32, block_size)
        s = jnp.where((slot < valid)[None, :], s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + p @ v_blk
        m_scr[...] = m_new

    @pl.when(j == blocks_per_seq - 1)
    def _flush():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def paged_decode_attention(
    q: jnp.ndarray,             # (B, Hq, D) — one token per sequence
    k_pool: jnp.ndarray,        # (NB, Hkv, BS, D) global block pool
    v_pool: jnp.ndarray,        # (NB, Hkv, BS, Dv)
    block_tables: jnp.ndarray,  # (B, NBseq) int32 pool block ids
    valid_len: jnp.ndarray,     # (B,) int32 — written tokens per sequence
    *,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    B, Hq, D = q.shape
    NB, Hkv, BS, Dv = v_pool.shape
    NBseq = block_tables.shape[1]
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    qg = q.reshape(B, Hkv, G, D)
    kernel = functools.partial(_paged_kernel, scale=scale, block_size=BS,
                               blocks_per_seq=NBseq)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                  # block tables + valid lens
        grid=(B, Hkv, NBseq),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, j, bt, vl: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, BS, D),
                         lambda b, h, j, bt, vl: (bt[b, j], h, 0, 0)),
            pl.BlockSpec((1, 1, BS, Dv),
                         lambda b, h, j, bt, vl: (bt[b, j], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, Dv),
                               lambda b, h, j, bt, vl: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, Dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, Dv), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), valid_len.astype(jnp.int32),
      qg, k_pool, v_pool)
    return out.reshape(B, Hq, Dv)


# ---------------------------------------------------------------------------
# chunked prefill-append


def _chunk_prefill_kernel(info_ref, bt_ref, q_ref, kp_ref, vp_ref, kn_ref,
                          vn_ref, o_ref, m_scr, l_scr, acc_scr, *,
                          scale: float, block_size: int, n_ctx: int,
                          group: int):
    """Grid (Hkv, n_ctx + 1): the sequential j dimension streams the
    sequence's cached context blocks (j < n_ctx) and finishes on the
    chunk's own KV (j == n_ctx), accumulating one online softmax across
    both — so a chunk's attention never materializes (Sb x history)."""
    j = pl.program_id(1)
    start = info_ref[0]                       # cached tokens (chunk offset)
    s_real = info_ref[1]                      # live (non-pad) chunk tokens

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    SbG, D = q_ref.shape[1] * q_ref.shape[2], q_ref.shape[3]
    q = (q_ref[0].astype(jnp.float32) * scale).reshape(SbG, D)
    # query row r of the flattened (Sb*G) tile belongs to chunk token r//G
    q_idx = jax.lax.broadcasted_iota(jnp.int32, (SbG, 1), 0) // group

    def online(s, v_blk):
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + p @ v_blk
        m_scr[...] = m_new

    # cached context: every token of this block below `start` is live for
    # every chunk query (it precedes the whole chunk). Skip blocks with
    # nothing cached — attending an all-masked block would poison the
    # online softmax (m stays -inf and exp(s - m) saturates to 1).
    @pl.when((j < n_ctx) & (j * block_size < start))
    def _ctx():
        k_blk = kp_ref[0, 0].astype(jnp.float32)            # (BS, D)
        v_blk = vp_ref[0, 0].astype(jnp.float32)
        s = q @ k_blk.T                                     # (SbG, BS)
        slot = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_size), 1)
        online(jnp.where(slot < start, s, NEG_INF), v_blk)

    # the chunk itself: causal within the chunk, pads masked out
    @pl.when(j == n_ctx)
    def _self():
        k_new = kn_ref[0].astype(jnp.float32)               # (Sb, D)
        v_new = vn_ref[0].astype(jnp.float32)
        s = q @ k_new.T                                     # (SbG, Sb)
        k_idx = jax.lax.broadcasted_iota(jnp.int32, (1, s.shape[1]), 1)
        live = (k_idx <= q_idx) & (k_idx < s_real)
        online(jnp.where(live, s, NEG_INF), v_new)

    @pl.when(j == n_ctx)
    def _flush():
        l = jnp.maximum(l_scr[...], 1e-30)
        out = (acc_scr[...] / l[:, None])
        o_ref[0] = out.reshape(o_ref.shape[1:]).astype(o_ref.dtype)


def paged_prefill_attention(
    q: jnp.ndarray,             # (Sb, Hq, D) one sequence's chunk queries
    k_pool: jnp.ndarray,        # (NB, Hkv, BS, D) global block pool
    v_pool: jnp.ndarray,        # (NB, Hkv, BS, Dv)
    k_new: jnp.ndarray,         # (Hkv, Sb, D) the chunk's fresh KV
    v_new: jnp.ndarray,         # (Hkv, Sb, Dv)
    block_table: jnp.ndarray,   # (NBctx,) int32 blocks holding the context
    start,                      # scalar int32: tokens already cached
    s_real,                     # scalar int32: live chunk tokens (<= Sb)
    *,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Chunk-append attention for continuous batching: the ``Sb`` chunk
    queries run at global positions ``start .. start+Sb-1`` against the
    sequence's cached blocks plus the chunk's own KV (causal). The chunk
    KV is an operand, not yet in the pool — the caller scatters it after
    (gather/compute/scatter, same split the paged engine prefill uses)."""
    Sb, Hq, D = q.shape
    NB, Hkv, BS, Dv = v_pool.shape
    if block_table.shape[0] == 0:       # no context yet: dummy (masked) block
        block_table = jnp.zeros((1,), jnp.int32)
    NBctx = block_table.shape[0]
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    qg = jnp.moveaxis(q.reshape(Sb, Hkv, G, D), 1, 0)   # (Hkv, Sb, G, D)
    info = jnp.stack([jnp.asarray(start, jnp.int32),
                      jnp.asarray(s_real, jnp.int32)])
    kernel = functools.partial(_chunk_prefill_kernel, scale=scale,
                               block_size=BS, n_ctx=NBctx, group=G)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                  # [start, s_real] + table
        grid=(Hkv, NBctx + 1),
        in_specs=[
            pl.BlockSpec((1, Sb, G, D), lambda h, j, info, bt: (h, 0, 0, 0)),
            pl.BlockSpec((1, 1, BS, D),
                         lambda h, j, info, bt:
                         (bt[jnp.minimum(j, NBctx - 1)], h, 0, 0)),
            pl.BlockSpec((1, 1, BS, Dv),
                         lambda h, j, info, bt:
                         (bt[jnp.minimum(j, NBctx - 1)], h, 0, 0)),
            pl.BlockSpec((1, Sb, D), lambda h, j, info, bt: (h, 0, 0)),
            pl.BlockSpec((1, Sb, Dv), lambda h, j, info, bt: (h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Sb, G, Dv),
                               lambda h, j, info, bt: (h, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Sb * G,), jnp.float32),
            pltpu.VMEM((Sb * G,), jnp.float32),
            pltpu.VMEM((Sb * G, Dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Hkv, Sb, G, Dv), q.dtype),
        interpret=interpret,
    )(info, block_table.astype(jnp.int32), qg, k_pool, v_pool, k_new, v_new)
    return jnp.moveaxis(out, 0, 1).reshape(Sb, Hq, Dv)
