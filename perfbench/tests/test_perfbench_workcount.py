"""Operation and byte counts against hand counts at a small shape, and
the device peak table."""
import pytest

from perfbench import peaks, workcount

CFG = {"num_layers": 2, "d_model": 8, "num_heads": 4, "num_kv_heads": 2,
       "head_dim": 4, "d_ff": 16, "vocab_size": 10}


def test_matmul_weights_by_hand():
    # per layer: wq 8x16 + wk 8x8 + wv 8x8 + wo 16x8 + 3 x 8x16; head 10x8
    per_layer = 128 + 64 + 64 + 128 + 3 * 128
    assert workcount.matmul_weights(CFG) == 2 * per_layer + 80


def test_decode_attention_by_hand():
    w = workcount.decode_attention(CFG, context=5)
    # QK^T and PV: 2 x (4 heads x 4 dims x 5 keys) multiply-adds x 2 FLOPs
    assert w.flops == 2 * 2 * (4 * 4 * 5) * 2
    # K and V: 5 tokens x 2 kv heads x 4 dims; query + output: 2 x 16
    assert w.bytes == (2 * 5 * 2 * 4 + 2 * 16) * 2 * 2


def test_prefill_attention_is_causal_and_chunking_free():
    w = workcount.prefill_attention(CFG, cached=2, prompt=5)
    # queries at positions 2, 3, 4 attend 3, 4, 5 keys
    assert w.flops == 4 * 4 * 4 * (3 + 4 + 5) * 2
    assert w.bytes == (2 * 5 * 2 * 4 + 2 * 3 * 16) * 2 * 2
    assert workcount.prefill_attention(CFG, cached=5, prompt=5) == \
        workcount.Work()
    # the whole prompt equals its parts' attention FLOPs
    a = workcount.prefill_attention(CFG, 0, 3).flops
    b = workcount.prefill_attention(CFG, 3, 5).flops
    assert a + b == workcount.prefill_attention(CFG, 0, 5).flops


def test_model_flops_and_roofline_time():
    attn = workcount.Work(flops=100.0, bytes=50.0)
    assert workcount.model_flops(CFG, 3, attn) == \
        2 * workcount.matmul_weights(CFG) * 3 + 100.0
    assert attn.least_seconds(10.0, 100.0) == 10.0      # compute-bound
    assert attn.least_seconds(1000.0, 10.0) == 5.0      # memory-bound


def test_peak_table_names_its_source_and_refuses_unknown_devices():
    p = peaks.peaks_for("TPU v5 lite")
    assert p.bf16_flops == 197e12 and p.hbm_bytes_per_s == 819e9
    assert "TPU v5e" in p.source
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
