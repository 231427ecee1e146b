"""The plain reference draws the program's weights again from the seed and
computes what the program computes: at float32 and a small size the two
agree to rounding. Its int8 controls read a wider gap than a program
that follows the reference, and the harness's check judges them not
correct."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import spec

sys.path.insert(0, os.path.join(os.path.dirname(spec.HERE), "src"))


def tiny(tie: bool, bias: bool):
    from repro.configs.base import ModelConfig
    mc = ModelConfig(name="t", num_layers=2, d_model=64, num_heads=4,
                     num_kv_heads=2, head_dim=16, d_ff=96, vocab_size=300,
                     use_qkv_bias=bias, tie_embeddings=tie, dtype="float32")
    cfg = {k: getattr(mc, k) for k in (
        "num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim",
        "d_ff", "vocab_size", "use_qkv_bias", "tie_embeddings", "norm_eps",
        "rope_theta")}
    return mc, cfg


@pytest.mark.parametrize("tie,bias", [(True, False), (False, True)])
def test_reference_matches_program_forward_at_float32(tie, bias):
    from repro.models import init_model
    from repro.models.transformer import lm_forward
    mc, cfg = tiny(tie, bias)
    seed = 2 ** 31 + 11
    params = init_model(mc, jax.random.PRNGKey(seed))
    toks = np.random.RandomState(0).randint(0, 300, 40)
    with jax.default_matmul_precision("highest"):
        logits, _ = lm_forward(params, mc, jnp.asarray(toks)[None],
                               remat=False)
    logits = np.asarray(logits[0])
    ref = spec.reference({"reference": "dense_gqa"})
    got = ref.logit_gaps(cfg, seed, [(list(toks), 10)],
                         controls=("w8", "w8a8"))[0]
    rows = np.arange(9, 39)
    want = logits[rows].max(-1) - logits[rows, toks[10:40]]
    np.testing.assert_allclose(got["gap"], want, atol=1e-5)
    # served tokens that follow the reference read no gap; the control,
    # in int8, picks other tokens at some positions
    greedy = np.array(toks)              # causal: later slots do not matter
    fwd = jax.jit(lambda t: lm_forward(params, mc, t[None], remat=False)[0])
    for p in range(10, 40):
        with jax.default_matmul_precision("highest"):
            greedy[p] = int(np.argmax(fwd(jnp.asarray(greedy))[0, p - 1]))
    greedy = list(greedy)
    own = ref.logit_gaps(cfg, seed, [(greedy, 10)],
                         controls=("w8", "w8a8"))[0]
    assert own["gap"].max() < 1e-5
    for c in ("w8", "w8a8"):
        assert own["control_gap." + c].max() > 10 * max(own["gap"].max(), 1e-6)


def test_int8_controls_are_not_correct():
    """Each int8 control, put in the program's place by the harness's own
    check on smollm-360m at its full size, fails the configuration's
    limit: at every position of the same prompts and tokens it serves
    its own first choice."""
    from perfbench import harness, traffic
    from perfbench.loadgen import Record
    bench = spec.Benchmark.load(os.path.dirname(spec.HERE))
    cell = bench.cell("smollm360m-zeroshot")
    cfg = bench.config(cell)
    seed = 2 ** 31 + 5
    rng = np.random.default_rng(seed)
    records = []
    for q in traffic.generate(bench.traffic(cell), seed, 10)[:4]:
        q.max_new_tokens = 100
        rec = Record(req=q, due_t=0.0, finish="length",
                     prompt_tokens=len(q.prompt.encode()))
        rec.tokens = [int(t) for t in rng.integers(32, 127, 100)]
        records.append(rec)
    _, info = harness.check(cfg, records, seed, seed % 2 ** 32,
                            controls=("w8", "w8a8"))
    for c in ("w8", "w8a8"):
        got = info["control." + c]
        assert got["correct"] is False
        assert not harness.is_correct(got["compared"])
        gap = got["compared"]["mean_logit_gap"]
        assert gap["value"] > gap["limit"] == 0.002
