"""Plain float32 reference of the dense GQA decoder the configurations state.

Imports nothing of the program and takes nothing it made. The weights
are drawn again from the run's seed with the recipe the configuration
names (``"weights": "repro-init-v1"``): one PRNG key split four ways,
normal draws scaled by 1/sqrt(fan-in), embeddings and head at 0.02,
norm scales one, biases zero, layer i drawn from the i-th split of the
second key. They are drawn one layer at a time, so the reference never
holds more than one layer besides the embeddings.

The forward pass is the textbook one at ``precision="highest"``:
RMSNorm, Q/K/V projections with optional bias, rotary position
embedding in the rotate-half layout over the whole head, grouped-query
causal softmax attention scaled by 1/sqrt(head_dim), output
projection, RMSNorm, SwiGLU FFN, final RMSNorm, and logits against the
embedding (tied) or the head.

``logit_gaps`` runs it teacher-forced over served sequences and returns,
for every served token, how far its reference logit lies below the
reference's best at that position. Given ``controls`` it also runs the
same model in int8 and returns, per control, the gap of the token that
the lower precision puts first: ``"w8"`` rounds every weight matrix to
int8 (symmetric, one scale per output channel); ``"w8a8"`` also rounds
every matmul's input rows to int8 (one scale per row).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

BUCKET = 256                    # sequence lengths are padded to multiples
HEAD_ROWS = 256                 # positions unembedded per call


def _normal(key, shape, scale):
    return jax.random.normal(key, shape, dtype=jnp.float32) * scale


@partial(jax.jit, static_argnums=(1,))
def _layer_weights(key, dims: Tuple[int, int, int, int, int, bool]):
    d, hq, hkv, hd, dff, bias = dims
    ka, kf = jax.random.split(key)
    a = jax.random.split(ka, 4)
    w = {"wq": _normal(a[0], (d, hq * hd), 1.0 / math.sqrt(d)),
         "wk": _normal(a[1], (d, hkv * hd), 1.0 / math.sqrt(d)),
         "wv": _normal(a[2], (d, hkv * hd), 1.0 / math.sqrt(d)),
         "wo": _normal(a[3], (hq * hd, d), 1.0 / math.sqrt(hq * hd))}
    f = jax.random.split(kf, 3)
    w["w_gate"] = _normal(f[0], (d, dff), 1.0 / math.sqrt(d))
    w["w_up"] = _normal(f[1], (d, dff), 1.0 / math.sqrt(d))
    w["w_down"] = _normal(f[2], (dff, d), 1.0 / math.sqrt(dff))
    if bias:
        w["bq"] = jnp.zeros((hq * hd,), jnp.float32)
        w["bk"] = jnp.zeros((hkv * hd,), jnp.float32)
        w["bv"] = jnp.zeros((hkv * hd,), jnp.float32)
    return w


@partial(jax.jit, static_argnums=(1, 2))
def _table(key, vocab: int, d: int):
    return _normal(key, (vocab, d), 0.02)


def _dims(cfg: dict):
    return (cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"],
            cfg["head_dim"], cfg["d_ff"], bool(cfg.get("use_qkv_bias")))


def _keys(cfg: dict, seed: int):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return ks, jax.random.split(ks[1], cfg["num_layers"])


@jax.jit
def _int8(w):
    """Round an (in, out) weight to int8, one symmetric scale per output
    channel."""
    s = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(w / s) * s


@jax.jit
def _int8_rows(t):
    """Round a (vocab, d) table to int8, one scale per row."""
    s = jnp.max(jnp.abs(t), axis=1, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(t / s) * s


def _quantize_layer(w: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
    return {k: (_int8(v) if v.ndim == 2 else v) for k, v in w.items()}


def _rmsnorm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta):
    """x: (S, H, D), positions 0..S-1, rotate-half over the whole head."""
    s, _, dh = x.shape
    half = dh // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _rows_int8(x):
    """Round each row of a matmul input to int8, one scale per row."""
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(x / s) * s


@partial(jax.jit, static_argnums=(2, 3))
def _layer(w, h, static, qact=False):
    """One decoder layer over a whole (padded) sequence h: (S, d);
    ``qact`` rounds every matmul input to int8 first."""
    hq, hkv, hd, eps, theta = static
    s = h.shape[0]
    a = _rows_int8 if qact else (lambda t: t)
    with jax.default_matmul_precision("highest"):
        x = a(_rmsnorm(h, eps))
        q, k, v = x @ w["wq"], x @ w["wk"], x @ w["wv"]
        if "bq" in w:
            q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
        q = _rope(q.reshape(s, hq, hd), theta)
        k = _rope(k.reshape(s, hkv, hd), theta)
        v = v.reshape(s, hkv, hd)
        g = hq // hkv
        k = jnp.repeat(k, g, axis=1)              # query head j -> kv j//g
        v = jnp.repeat(v, g, axis=1)
        att = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
        causal = jnp.tril(jnp.ones((s, s), bool))
        att = jnp.where(causal[None], att, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(att, axis=-1), v)
        h = h + a(o.reshape(s, hq * hd)) @ w["wo"]
        x = a(_rmsnorm(h, eps))
        y = a(jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]
    return h + y


@partial(jax.jit, static_argnums=(3,))
def _head(h_rows, table, served, eps):
    """Reference logits of ``h_rows``: the best logit and the served
    token's logit at each row."""
    with jax.default_matmul_precision("highest"):
        logits = _rmsnorm(h_rows, eps) @ table.T
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
    return best, got


@partial(jax.jit, static_argnums=(4, 5))
def _head_control(h_ref, h_ctl, table, table_ctl, eps, qact):
    """Gap, in reference logits, of the token the control puts first."""
    a = _rows_int8 if qact else (lambda t: t)
    with jax.default_matmul_precision("highest"):
        ref = _rmsnorm(h_ref, eps) @ table.T
        ctl = a(_rmsnorm(h_ctl, eps)) @ table_ctl.T
    pick = jnp.argmax(ctl, axis=-1)
    return (jnp.max(ref, axis=-1)
            - jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0])


CONTROLS = {"w8": False, "w8a8": True}      # name -> int8 activations


def logit_gaps(cfg: dict, seed: int,
               seqs: Sequence[Tuple[Sequence[int], int]],
               controls: Sequence[str] = ()) -> List[dict]:
    """``seqs``: (token ids, n_prompt) per served request: the prompt as
    served, then the served tokens. For each, returns ``{"gap": ...}``
    (one entry per served token: reference best logit minus the served
    token's reference logit) and ``{"control_gap.<name>": ...}`` for each
    control (the same for the control's first choice at each position)."""
    static = (cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"],
              float(cfg["norm_eps"]), float(cfg["rope_theta"]))
    eps = float(cfg["norm_eps"])
    vocab, d = cfg["vocab_size"], cfg["d_model"]
    ks, layer_keys = _keys(cfg, seed)
    embed = _table(ks[0], vocab, d)
    table = embed if cfg["tie_embeddings"] else _table(ks[3], vocab, d)
    ids = []
    for toks, _ in seqs:
        t = np.asarray(toks, np.int32)
        ids.append(np.concatenate([t, np.zeros(-len(t) % BUCKET, np.int32)]))
    hs = [embed[jnp.asarray(t)] for t in ids]
    hc: Dict[str, list] = {}
    if controls:
        embed_c = _int8_rows(embed)
        table_c = embed_c if cfg["tie_embeddings"] else _int8_rows(table)
        hc = {c: [embed_c[jnp.asarray(t)] for t in ids] for c in controls}
    for i in range(cfg["num_layers"]):
        w = _layer_weights(layer_keys[i], _dims(cfg))
        hs = [_layer(w, h, static) for h in hs]
        if controls:
            wq = _quantize_layer(w)
            for c in controls:
                hc[c] = [_layer(wq, h, static, CONTROLS[c]) for h in hc[c]]
        del w
    out = []
    for j, (toks, n_prompt) in enumerate(seqs):
        # the logits at position p predict token p + 1
        rows = np.arange(n_prompt - 1, len(toks) - 1)
        gap, cgap = [], {c: [] for c in controls}
        for a in range(0, len(rows), HEAD_ROWS):
            r = rows[a:a + HEAD_ROWS]
            served = jnp.asarray(np.asarray(toks, np.int32)[r + 1])
            best, got = _head(hs[j][r], table, served, eps)
            gap.append(np.asarray(best - got))
            for c in controls:
                cgap[c].append(np.asarray(_head_control(
                    hs[j][r], hc[c][j][r], table, table_c, eps,
                    CONTROLS[c])))
        rec = {"gap": np.concatenate(gap)}
        for c in controls:
            rec["control_gap." + c] = np.concatenate(cgap[c])
        out.append(rec)
    return out
