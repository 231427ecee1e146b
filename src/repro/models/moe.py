"""Mixture-of-Experts FFN: shared experts + fine-grained routed top-k.

Implementation: capacity-based scatter dispatch (static shapes, SPMD
friendly, differentiable):

  1. router softmax over experts; top-k per token (weights renormalized);
  2. per-(token, k) slot position inside its expert via a cumsum rank over
     the flattened token axis; tokens past ``capacity`` are dropped
     (their combine weight contributes nothing — residual carries them);
  3. scatter tokens into an (E, C, d) buffer; one batched einsum per
     FFN matrix runs every expert on its C slots — compute scales with
     topk * tokens * capacity_factor, NOT with num_experts;
  4. gather + weighted combine back to (B, S, d).

The expert dimension shards over the ``model`` mesh axis (expert
parallelism); XLA lowers the scatter/gather into the all-to-all pattern.
Aux load-balance loss follows Switch/DeepSeek: E * sum_e f_e * p_e.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import Params, act_fn, cast_weight, dense_init


def init_moe(cfg: ModelConfig, key, dtype=jnp.float32) -> Params:
    d, e, f = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    scale = 1.0 / jnp.sqrt(d)

    def ew(k, a, b):
        return (jax.random.normal(k, (e, a, b), jnp.float32) / jnp.sqrt(a)).astype(dtype)

    p = {
        "router": dense_init(ks[0], d, e, jnp.float32),   # router kept f32
        "w_gate": ew(ks[1], d, f),
        "w_up": ew(ks[2], d, f),
        "w_down": ew(ks[3], f, d),
    }
    if cfg.num_shared_experts:
        from repro.models.mlp import init_ffn
        p["shared"] = init_ffn(cfg, ks[4], d_ff=cfg.num_shared_experts * f, dtype=dtype)
    return p


def moe_ffn(
    params: Params,
    cfg: ModelConfig,
    x: jnp.ndarray,                 # (B, S, d)
    *,
    capacity_factor: float = 1.25,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (out, aux_loss).

    ``capacity_factor=None`` means no-drop: capacity = T (worst case every
    token routes one of its top-k picks to the same expert). Used for
    decode steps, where T is small and exactness matters more than the
    dispatch-buffer size.
    """
    B, S, d = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    T = B * S
    xt = x.reshape(T, d)
    act = act_fn(cfg.act)

    logits = xt.astype(jnp.float32) @ params["router"]          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, K)                      # (T, K)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # aux load-balance loss (Switch-style on full probs + top-k counts)
    one_hot = jax.nn.one_hot(top_e, E, dtype=jnp.float32)       # (T, K, E)
    frac_tokens = one_hot.sum(axis=(0, 1)) / (T * K)
    frac_probs = probs.mean(axis=0)
    aux = E * jnp.sum(frac_tokens * frac_probs)

    # position of each (token, k) inside its expert queue
    flat_e = top_e.reshape(T * K)                               # token-major
    oh = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)             # (T*K, E)
    pos_in_e = (jnp.cumsum(oh, axis=0) - oh)                    # exclusive
    pos = jnp.take_along_axis(pos_in_e, flat_e[:, None], axis=1)[:, 0]

    if capacity_factor is None:
        C = T
    else:
        C = max(1, min(T, int(capacity_factor * T * K / E)))
    keep = pos < C
    w = top_p.reshape(T * K) * keep                             # dropped -> 0

    # scatter tokens into (E, C, d)
    tok_idx = jnp.repeat(jnp.arange(T), K)
    buf = jnp.zeros((E, C, d), x.dtype)
    safe_pos = jnp.where(keep, pos, C - 1)
    buf = buf.at[flat_e, safe_pos].add(
        jnp.where(keep[:, None], xt[tok_idx], 0).astype(x.dtype))

    # expert FFN on (E, C, d)
    h = act(jnp.einsum("ecd,edf->ecf", buf, cast_weight(params["w_gate"], x.dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", buf, cast_weight(params["w_up"], x.dtype))
    y = jnp.einsum("ecf,efd->ecd", h, cast_weight(params["w_down"], x.dtype))

    # gather + combine
    y_tok = y[flat_e, safe_pos]                                 # (T*K, d)
    contrib = y_tok * w[:, None].astype(x.dtype)
    out = jnp.zeros((T, d), x.dtype).at[tok_idx].add(contrib)

    if "shared" in params:
        from repro.models.mlp import ffn
        out = out + ffn(params["shared"], cfg, xt)
    return out.reshape(B, S, d), aux.astype(jnp.float32)
