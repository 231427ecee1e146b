"""Serving params: layer matmul weights stored in the compute dtype.

``serving_params`` casts, once, each layer weight that the model reads
only through ``cast_weight`` into the compute dtype. Checked here on the
CPU: the paged chunk prefill and fused decode step give the same logits
and tokens as with float32 storage, exactly the intended leaves change
dtype, and a pool spin-up keeps no float32 copy and reports the share.
"""
import dataclasses
import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import reduced_f32
from repro.configs.registry import ARCHS
from repro.core.gateway import ServeFrontend
from repro.core.orchestrator import SpinConfig
from repro.models import init_model, serving_params
from repro.models.transformer import init_paged_cache
from repro.serving.backend import BACKENDS
from repro.serving.engine import compile_paged_fns, init_device_state
from test_trace_phases import _host_spans, _parent

BS = 16
MAX_SEQ = 64
NBSEQ = MAX_SEQ // BS
PROMPTS = (19, 11)             # live tokens of each sequence's one chunk
STEPS = 4

GQA = ("wq", "wk", "wv", "wo")
BIAS = ("bq", "bk", "bv")
FFN = ("w_gate", "w_up", "w_down")


def _dtypes(tree) -> dict:
    """'layers/attn/wq' -> dtype name, for every leaf."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path): str(leaf.dtype)
            for path, leaf in flat}


def _containers(params):
    """New containers over the same arrays (``serving_params`` converts
    in place)."""
    return jax.tree_util.tree_map(lambda x: x, params)


def _serve(cfg, fns, params):
    """One chunk prefill per sequence, then STEPS fused greedy decode
    steps and one paged decode. Returns every logit and token."""
    be = BACKENDS["vllm"]
    cache = init_paged_cache(cfg, len(PROMPTS) * NBSEQ, BS)
    state = init_device_state(be.max_batch, NBSEQ)
    tables = np.zeros((be.max_batch, NBSEQ), np.int32)
    out = []
    for row, n in enumerate(PROMPTS):
        table = jnp.arange(row * NBSEQ, (row + 1) * NBSEQ, dtype=jnp.int32)
        toks = jax.random.randint(jax.random.PRNGKey(row + 1), (1, 32), 1,
                                  cfg.vocab_size, jnp.int32)
        ctx = fns.gather(cache, table[:1])
        logits, kv = fns.prefill(params, toks, ctx, jnp.int32(0),
                                 jnp.int32(n))
        cache = fns.scatter(cache, kv, table, jnp.int32(0), jnp.int32(n))
        out.append(logits)
        tables[row] = np.asarray(table)
        first = jnp.argmax(logits[0]).astype(jnp.int32)
        state = dict(state,
                     tokens=state["tokens"].at[row, 0].set(first),
                     pos=state["pos"].at[row].set(n),
                     active=state["active"].at[row].set(True),
                     max_new=state["max_new"].at[row].set(STEPS + 2))
    state = dict(state, tables=jnp.asarray(tables))
    for _ in range(STEPS):
        nxt, _, cache, state = fns.fused_step(params, cache, state)
        out.append(nxt)
    pos = jnp.where(state["active"], state["pos"], -1)
    logits, _ = fns.decode(params, state["tokens"], cache, state["tables"],
                           pos)
    out.append(logits)
    return out


@pytest.mark.parametrize("arch", ["glm4-9b", "smollm-360m"])
def test_served_params_give_the_same_logits_and_tokens(arch):
    cfg = ARCHS[arch].reduced()
    assert cfg.dtype == "bfloat16"
    fns = compile_paged_fns(cfg, BACKENDS["vllm"], MAX_SEQ, BS)
    params = init_model(cfg, jax.random.PRNGKey(0))
    served = serving_params(cfg, _containers(params))
    assert "bfloat16" in _dtypes(served).values()
    assert set(_dtypes(params).values()) == {"float32"}
    for a, b in zip(_serve(cfg, fns, params), _serve(cfg, fns, served),
                    strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _under(prefix, names):
    return {f"{prefix}/{n}" for n in names}


# converted leaves of each family's reduced config; every other leaf
# (embeddings, head, norms, router, MLA, SSM, hybrid, enc-dec) stays f32
CONVERTED = {
    "glm4-9b": _under("layers/attn", GQA + BIAS) | _under("layers/ffn", FFN),
    "smollm-360m": _under("layers/attn", GQA) | _under("layers/ffn", FFN),
    "deepseek-moe-16b": (
        _under("layers/attn", GQA) | _under("layers/ffn", FFN)
        | _under("layers/ffn/shared", FFN)
        | _under("prefix_layers/0/attn", GQA)
        | _under("prefix_layers/0/ffn", FFN)),
    "deepseek-v2-236b": (
        _under("layers/ffn", FFN) | _under("layers/ffn/shared", FFN)
        | _under("prefix_layers/0/ffn", FFN)),
    "mamba2-2.7b": set(),
    "zamba2-1.2b": set(),
    "seamless-m4t-medium": set(),
}


@pytest.mark.parametrize("arch", sorted(CONVERTED))
def test_only_layer_matmul_weights_change_dtype(arch):
    cfg = ARCHS[arch].reduced()
    served = _dtypes(jax.eval_shape(
        lambda k: serving_params(cfg, init_model(cfg, k)),
        jax.random.PRNGKey(0)))
    low = {p for p, d in served.items() if d != "float32"}
    assert low == CONVERTED[arch]
    assert {served[p] for p in low} <= {"bfloat16"}
    kept = [p for p in served
            if p in ("embed", "lm_head") or "norm" in p or "router" in p]
    assert "embed" in kept
    assert all(served[p] == "float32" for p in kept)
    if cfg.has_moe:
        assert "layers/ffn/router" in kept


def test_float32_compute_keeps_params_as_they_are():
    cfg = reduced_f32("glm4-9b")
    params = init_model(cfg, jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_leaves(params)
    out = serving_params(cfg, params)
    assert out is params
    assert all(a is b for a, b in zip(jax.tree_util.tree_leaves(out), leaves,
                                      strict=True))


def test_pool_stores_served_params_and_sets_the_share(tmp_path):
    # odd widths and depth, so no other test's arrays share these shapes
    cfg = dataclasses.replace(ARCHS["smollm-360m"].reduced(), num_layers=3,
                              d_ff=200)
    d, hd, L = cfg.d_model, cfg.head_dim, cfg.num_layers
    attn = 2 * d * cfg.num_heads * hd + 2 * d * cfg.num_kv_heads * hd
    low = L * (attn + 3 * d * cfg.d_ff)
    total = low + L * 2 * d + d + cfg.vocab_size * d   # norms, tied embed
    spin = SpinConfig(window_s=20.0, cooldown_s=0.0, idle_tau_s=0.5,
                      tick_s=3600.0, max_replicas=1,
                      warm_pool={"small": 0, "medium": 0, "large": 0})
    fe = ServeFrontend({"smollm-360m": cfg}, max_seq=64, spin=spin,
                       paged=True)
    with jax.profiler.trace(str(tmp_path)):
        fe.pool.scale("smollm-360m", "trt", 1)
    spans = _host_spans(str(tmp_path))
    store, = [s for s in spans if s[0] == "pool.store_params"]
    assert _parent(store, spans) == "pool.spin"
    reg = fe.obs.registry
    assert reg.value("params_compute_dtype_share",
                     "smollm-360m") == pytest.approx(low / total)
    warm = fe.pool._params["smollm-360m"]
    stored = _dtypes(warm)
    assert {p for p, t in stored.items() if t == "bfloat16"} == \
        CONVERTED["smollm-360m"]
    # no float32 array of a converted leaf's shape outlives the cast
    flat = dict(zip(_dtypes(warm), jax.tree_util.tree_leaves(warm)))
    shapes = {flat[p].shape for p in CONVERTED["smollm-360m"]}
    gc.collect()
    assert not [a for a in jax.live_arrays()
                if a.dtype == jnp.float32 and a.shape in shapes]
    h = fe.submit("sum the numbers", max_new_tokens=3)
    fe.serve_all()
    assert h.response.completed
