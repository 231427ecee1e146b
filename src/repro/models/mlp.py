"""Feed-forward blocks: SwiGLU (gate/up/down) and GELU MLP."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import Params, act_fn, cast_weight, dense_init


def init_ffn(cfg: ModelConfig, key, d_ff: int = 0, dtype=jnp.float32) -> Params:
    d_ff = d_ff or cfg.d_ff
    d = cfg.d_model
    if cfg.act == "silu":
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "w_gate": dense_init(k1, d, d_ff, dtype),
            "w_up": dense_init(k2, d, d_ff, dtype),
            "w_down": dense_init(k3, d_ff, d, dtype),
        }
    k1, k2 = jax.random.split(key, 2)
    return {
        "w_up": dense_init(k1, d, d_ff, dtype),
        "w_down": dense_init(k2, d_ff, d, dtype),
    }


def ffn(params: Params, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    act = act_fn(cfg.act)
    if "w_gate" in params:
        h = (act(x @ cast_weight(params["w_gate"], x.dtype))
             * (x @ cast_weight(params["w_up"], x.dtype)))
    else:
        h = act(x @ cast_weight(params["w_up"], x.dtype))
    return h @ cast_weight(params["w_down"], x.dtype)
