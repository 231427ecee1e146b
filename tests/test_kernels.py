"""Per-kernel shape/dtype sweeps, assert_allclose vs the ref.py oracles.

All kernels run in interpret mode on CPU (the kernel body executes as
traced JAX), which validates indexing, masking, accumulator and BlockSpec
logic — everything except Mosaic codegen itself.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.RandomState(0)


def _rand(shape, dtype):
    x = RNG.randn(*shape).astype(np.float32)
    return jnp.asarray(x, dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Hq,Hkv,Sq,Skv,D", [
    (1, 2, 2, 32, 32, 32),      # MHA, square
    (2, 4, 2, 64, 64, 64),      # GQA 2:1
    (1, 8, 1, 32, 64, 32),      # MQA, Sq != Skv
    (2, 6, 2, 96, 96, 128),     # non-pow2 heads, MXU-width head dim
])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 24), (False, None)])
def test_flash_attention(B, Hq, Hkv, Sq, Skv, D, causal, window, dtype):
    q = _rand((B, Hq, Sq, D), dtype)
    k = _rand((B, Hkv, Skv, D), dtype)
    v = _rand((B, Hkv, Skv, D), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=16, block_k=16, interpret=True)
    want = ref.ref_attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Hq,Hkv,S,D", [
    (1, 4, 4, 64, 32),
    (3, 8, 2, 128, 64),
    (2, 16, 1, 64, 128),
])
@pytest.mark.parametrize("ring", [False, True])
def test_decode_attention(B, Hq, Hkv, S, D, ring, dtype):
    q = _rand((B, Hq, D), dtype)
    kc = _rand((B, Hkv, S, D), dtype)
    vc = _rand((B, Hkv, S, D), dtype)
    # mix of partially-filled and overflowing (ring) valid lengths
    vl = jnp.asarray(RNG.randint(1, 2 * S, size=(B,)), jnp.int32) if ring \
        else jnp.asarray(RNG.randint(1, S + 1, size=(B,)), jnp.int32)
    out = ops.decode_attention(q, kc, vc, vl, ring=ring, block_k=16,
                               interpret=True)
    want = ref.ref_decode_attention(q, kc, vc, vl, ring=ring)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Hq,Hkv,D,BS,NBseq,NB", [
    (1, 4, 4, 32, 16, 4, 8),        # MHA, small pool
    (3, 8, 2, 64, 16, 4, 24),       # GQA 4:1, tables permute the pool
    (2, 16, 1, 128, 32, 2, 6),      # MQA, MXU-width head dim
    (4, 6, 2, 32, 8, 6, 32),        # non-pow2 heads, more blocks than used
])
def test_paged_decode_attention(B, Hq, Hkv, D, BS, NBseq, NB, dtype):
    q = _rand((B, Hq, D), dtype)
    k_pool = _rand((NB, Hkv, BS, D), dtype)
    v_pool = _rand((NB, Hkv, BS, D), dtype)
    # each sequence leases distinct blocks scattered through the pool;
    # overlapping leases (shared prefix) are exercised by reusing seq 0's
    # first block for every sequence
    tables = np.stack([RNG.permutation(NB)[:NBseq] for _ in range(B)])
    tables[:, 0] = tables[0, 0]
    tables = jnp.asarray(tables, jnp.int32)
    vl = jnp.asarray(RNG.randint(1, NBseq * BS + 1, size=(B,)), jnp.int32)
    out = ops.paged_decode_attention(q, k_pool, v_pool, tables, vl,
                                     interpret=True)
    want = ref.ref_paged_decode_attention(q, k_pool, v_pool, tables, vl)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("Sb,Hq,Hkv,D,BS,NBctx,NB,start,s_real", [
    (16, 4, 4, 32, 16, 4, 8, 48, 16),     # MHA, full chunk, deep context
    (32, 8, 2, 64, 16, 4, 24, 24, 20),    # GQA 4:1, padded chunk, ragged ctx
    (8, 16, 1, 128, 32, 2, 6, 0, 5),      # MQA, NO cached context yet
    (16, 6, 2, 32, 8, 6, 32, 41, 16),     # non-pow2 heads, mid-block start
])
def test_paged_prefill_attention(Sb, Hq, Hkv, D, BS, NBctx, NB, start,
                                 s_real, dtype):
    q = _rand((Sb, Hq, D), dtype)
    k_pool = _rand((NB, Hkv, BS, D), dtype)
    v_pool = _rand((NB, Hkv, BS, D), dtype)
    k_new = _rand((Hkv, Sb, D), dtype)
    v_new = _rand((Hkv, Sb, D), dtype)
    table = jnp.asarray(RNG.permutation(NB)[:NBctx], jnp.int32)
    out = ops.paged_prefill_attention(q, k_pool, v_pool, k_new, v_new,
                                      table, start, s_real, interpret=True)
    want = ref.ref_paged_prefill_attention(q, k_pool, v_pool, k_new, v_new,
                                           table, start, s_real)
    # pad rows (>= s_real) are garbage by contract; compare live rows
    np.testing.assert_allclose(np.asarray(out, np.float32)[:s_real],
                               np.asarray(want, np.float32)[:s_real],
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_chunked_prefill_iterates_to_full_attention():
    """Appending a sequence chunk by chunk — each chunk attending the
    blocks written so far plus itself — reproduces whole-prompt causal
    attention exactly. This is the engine's chunked-prefill contract."""
    S, Hq, Hkv, D, BS, chunk = 64, 4, 2, 32, 16, 16
    q = _rand((S, Hq, D), jnp.float32)
    k = _rand((S, Hkv, D), jnp.float32)
    v = _rand((S, Hkv, D), jnp.float32)
    NB = S // BS + 1
    k_pool = jnp.zeros((NB, Hkv, BS, D), jnp.float32)
    v_pool = jnp.zeros((NB, Hkv, BS, D), jnp.float32)
    table = jnp.asarray(RNG.permutation(NB - 1) + 1, jnp.int32)  # 0 unused
    outs = []
    for start in range(0, S, chunk):
        sl = slice(start, start + chunk)
        outs.append(ops.paged_prefill_attention(
            q[sl], k_pool, v_pool, jnp.moveaxis(k[sl], 0, 1),
            jnp.moveaxis(v[sl], 0, 1), table, start, chunk, interpret=True))
        # scatter the chunk's KV into its blocks for the next iteration
        pos = start + np.arange(chunk)
        blk, off = table[pos // BS], pos % BS
        k_pool = k_pool.at[blk, :, off].set(k[sl])
        v_pool = v_pool.at[blk, :, off].set(v[sl])
    got = jnp.concatenate(outs, axis=0)                  # (S, Hq, D)
    want = ref.ref_attention(q.transpose(1, 0, 2)[None],
                             k.transpose(1, 0, 2)[None],
                             v.transpose(1, 0, 2)[None], causal=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want[0].transpose(1, 0, 2)),
                               atol=2e-5, rtol=2e-5)


def test_paged_decode_matches_dense_decode():
    """A paged cache whose block table is the identity equals the dense
    decode kernel on the same data — the paging is layout, not math."""
    B, Hq, Hkv, D, BS, NBseq = 2, 8, 2, 64, 16, 4
    S = BS * NBseq
    q = _rand((B, Hq, D), jnp.float32)
    kc = _rand((B, Hkv, S, D), jnp.float32)
    vc = _rand((B, Hkv, S, D), jnp.float32)
    vl = jnp.asarray([S - 5, 17], jnp.int32)
    # (B, Hkv, S, D) -> per-sequence blocks stacked into one pool
    def to_pool(c):
        blocks = jnp.moveaxis(c.reshape(B, Hkv, NBseq, BS, D), 2, 1)
        return blocks.reshape(B * NBseq, Hkv, BS, D)
    tables = jnp.arange(B * NBseq, dtype=jnp.int32).reshape(B, NBseq)
    out = ops.paged_decode_attention(q, to_pool(kc), to_pool(vc), tables, vl,
                                     interpret=True)
    want = ops.decode_attention(q, kc, vc, vl, block_k=16, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("B,L,H,P,N,chunk", [
    (1, 32, 2, 8, 8, 8),
    (2, 64, 3, 16, 16, 16),
    (1, 128, 2, 32, 32, 32),
    (2, 48, 4, 16, 8, 16),      # L not a multiple of a larger chunk
])
def test_ssd_scan(B, L, H, P, N, chunk):
    x = _rand((B, L, H, P), jnp.float32)
    dt = jnp.asarray(np.abs(RNG.randn(B, L, H)) * 0.1 + 0.01, jnp.float32)
    A = -jnp.asarray(np.abs(RNG.randn(H)) + 0.3, jnp.float32)
    Bm = _rand((B, L, H, N), jnp.float32)
    Cm = _rand((B, L, H, N), jnp.float32)
    y, st = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    yr, str_ = ref.ref_ssd(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=3e-5, rtol=3e-5)
    np.testing.assert_allclose(np.asarray(st), np.asarray(str_), atol=3e-5, rtol=3e-5)


def test_ssd_scan_matches_model_chunked():
    """Pallas kernel == the pure-jnp chunked SSD used by the model trunk."""
    from repro.models.ssm import ssd_chunked
    B, L, H, P, N = 2, 64, 2, 16, 8
    x = _rand((B, L, H, P), jnp.float32)
    dt = jnp.asarray(np.abs(RNG.randn(B, L, H)) * 0.1, jnp.float32)
    A = -jnp.asarray(np.abs(RNG.randn(H)) + 0.3, jnp.float32)
    Bm = _rand((B, L, H, N), jnp.float32)
    Cm = _rand((B, L, H, N), jnp.float32)
    y1, s1 = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=16, interpret=True)
    y2, s2 = ssd_chunked(x, dt, A, Bm, Cm, chunk=16)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# kernel-dispatch registry: the Pallas kernels ARE the engine hot path


def test_kernel_mode_defaults_to_reference_on_cpu():
    assert jax.default_backend() != "tpu"
    assert ops.kernel_mode() == "reference"
    with ops.kernel_dispatch("interpret"):
        assert ops.kernel_mode() == "interpret"
    assert ops.kernel_mode() == "reference"
    with pytest.raises(ValueError):
        ops.set_kernel_mode("vulkan")


def test_engine_dispatches_pallas_kernels_token_for_token():
    """A paged engine traced under ``interpret`` dispatch runs the real
    Pallas kernel bodies for BOTH chunk prefill and decode, and emits
    exactly the reference trunk's greedy tokens — the contract that lets
    TPU swap in Mosaic without touching the engine."""
    import dataclasses

    from repro.configs.registry import ARCHS
    from repro.models import init_model
    from repro.serving import (PagedInferenceEngine, Request, SamplingParams,
                               get_backend)
    cfg = dataclasses.replace(ARCHS["smollm-360m"].reduced(), dtype="float32")
    params = init_model(cfg, jax.random.PRNGKey(0))
    bk = get_backend("trt")

    def run(mode, burst=1):
        rng = np.random.RandomState(3)
        reqs = [Request(uid=i, tokens=list(rng.randint(0, cfg.vocab_size, L)),
                        sampling=SamplingParams(max_new_tokens=5))
                for i, L in enumerate([5, 16, 33])]
        with ops.kernel_dispatch(mode):        # read at trace time
            eng = PagedInferenceEngine(cfg, params, bk, max_seq=96,
                                       block_size=16, chunk_tokens=8,
                                       decode_burst=burst)
            return {r.uid: r.new_tokens for r in eng.run(reqs)}

    reference = run("reference")
    assert run("interpret") == reference
    assert run("interpret", burst=4) == reference
