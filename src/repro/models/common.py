"""Shared building blocks for the model zoo.

Pure-functional JAX: params are nested dicts of jnp arrays; every module is
an ``init_*`` + ``apply`` function pair. Layer stacks are stored with a
leading ``num_layers`` axis so the trunk can run under ``jax.lax.scan``
(small HLO, tractable compile times at 64 layers — see DESIGN.md §5).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

Params = dict


def cast_weight(w: jnp.ndarray, dtype) -> jnp.ndarray:
    """A stored weight in the compute dtype, under the ``weight_cast``
    name scope (a no-op where it already has that dtype)."""
    with jax.named_scope("weight_cast"):
        return w.astype(dtype)


# ---------------------------------------------------------------------------
# init helpers


def dense_init(key, in_dim: int, out_dim: int, dtype=jnp.float32, scale: Optional[float] = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(in_dim)
    return (jax.random.normal(key, (in_dim, out_dim), dtype=jnp.float32) * scale).astype(dtype)


def embed_init(key, vocab: int, dim: int, dtype=jnp.float32):
    return (jax.random.normal(key, (vocab, dim), dtype=jnp.float32) * 0.02).astype(dtype)


def stack_init(key, n: int, init_fn):
    """Initialize ``n`` layers and stack each leaf along axis 0 (for scan)."""
    keys = jax.random.split(key, n)
    layers = [init_fn(k) for k in keys]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs, axis=0), *layers)


# ---------------------------------------------------------------------------
# norms


def init_rmsnorm(dim: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((dim,), dtype=dtype)}


def rmsnorm(params: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * params["scale"].astype(jnp.float32)).astype(dtype)


def init_layernorm(dim: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((dim,), dtype=dtype), "bias": jnp.zeros((dim,), dtype=dtype)}


def layernorm(params: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    return y.astype(dtype)


# ---------------------------------------------------------------------------
# rotary embeddings


def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    """Inverse frequencies, shape (head_dim // 2,). float32."""
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def rope_cos_sin(positions: jnp.ndarray, head_dim: int, theta: float) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin tables for integer positions of any shape -> (*pos, half)."""
    inv = rope_freqs(head_dim, theta)
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """x: (..., seq, heads, head_dim); cos/sin: (..., seq, half).

    Rotates the (paired-halves) convention: x = [x1, x2] -> [x1*c - x2*s,
    x2*c + x1*s], matching llama-style RoPE.
    """
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos = cos[..., None, :].astype(x.dtype)  # broadcast over heads axis
    sin = sin[..., None, :].astype(x.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def mrope_cos_sin(
    positions: jnp.ndarray,      # (..., seq, 3) — (t, h, w) triplets
    sections: Tuple[int, ...],   # per-section half-dims, sum = head_dim // 2
    theta: float,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Qwen2-VL M-RoPE: the rotary half-dims are split into (t, h, w)
    sections, each rotated by the corresponding position coordinate.
    Text tokens use t == h == w, which reduces to standard RoPE.
    Returns cos/sin of shape (..., seq, head_dim // 2).
    """
    head_dim = 2 * sum(sections)
    inv = rope_freqs(head_dim, theta)           # (half,)
    # section id per frequency slot
    cos_parts, sin_parts = [], []
    start = 0
    for i, sec in enumerate(sections):
        pos_i = positions[..., i].astype(jnp.float32)          # (..., seq)
        ang = pos_i[..., None] * inv[start:start + sec]
        cos_parts.append(jnp.cos(ang))
        sin_parts.append(jnp.sin(ang))
        start += sec
    return jnp.concatenate(cos_parts, axis=-1), jnp.concatenate(sin_parts, axis=-1)


# ---------------------------------------------------------------------------
# activations


def act_fn(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return jax.nn.gelu
    raise ValueError(name)


def softcap(x: jnp.ndarray, cap: Optional[float]) -> jnp.ndarray:
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)
