"""Unified model API: family dispatch for init / forward / prefill / decode.

Batch dicts (see ``launch.specs.input_specs`` for the dry-run versions):
  dense | moe        {"tokens": (B, S) i32}
  vlm                {"tokens": (B, S_text) i32,
                      "vision_embeds": (B, F, d) — stubbed ViT output,
                      "positions": (B, F + S_text, 3) M-RoPE triplets}
  encdec (audio)     {"tokens": (B, S) i32,
                      "src_embeds": (B, F, d) — stubbed audio frontend}
  ssm | hybrid       {"tokens": (B, S) i32}
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import encdec, hybrid, ssm_lm, transformer
from repro.models.common import Params


def init_model(cfg: ModelConfig, key, dtype=jnp.float32) -> Params:
    if cfg.family == "ssm":
        return ssm_lm.init_ssm_lm(cfg, key, dtype)
    if cfg.family == "hybrid":
        return hybrid.init_hybrid(cfg, key, dtype)
    if cfg.family == "encdec":
        return encdec.init_encdec(cfg, key, dtype)
    return transformer.init_lm(cfg, key, dtype)


# Layer weights the model reads only through ``cast_weight(w, <compute
# dtype>)``, by the sub-block that holds them: the GQA projections and
# the FFN matrices (dense, shared-expert or routed-expert).
_GQA_WEIGHTS = frozenset({"wq", "wk", "wv", "wo", "bq", "bk", "bv", "bo"})
_FFN_WEIGHTS = frozenset({"w_gate", "w_up", "w_down"})


def serving_params(cfg: ModelConfig, params: Params) -> Params:
    """``params`` as a serving replica keeps them: each weight of
    ``layers``/``prefix_layers`` that the model reads only through
    ``cast_weight`` into the compute dtype is stored in that dtype, so
    no step casts it again. The matmuls see the same operands. All else
    keeps its dtype: embeddings and head, norms, the MoE router, MLA
    projections and the SSM / hybrid / enc-dec families.

    Converts in place, leaf by leaf: each float32 array is dropped as
    its cast is stored, so the peak stays one leaf above the float32
    params. Returns ``params`` unchanged where the compute dtype is
    float32."""
    dtype = transformer._adtype(cfg)
    if dtype == jnp.float32:
        return params
    roles = {"ffn": _FFN_WEIGHTS}
    if cfg.attention_type == "gqa":
        roles["attn"] = _GQA_WEIGHTS

    def cast(tree: Params, names) -> None:
        for k, v in tree.items():
            if isinstance(v, dict):
                cast(v, names)              # the MoE's shared experts
            elif k in names:
                tree[k] = v.astype(dtype)

    stack = [params["layers"]] if "layers" in params else []
    for block in stack + params.get("prefix_layers", []):
        for role, names in roles.items():
            if role in block:
                cast(block[role], names)
    return params


def model_forward(params: Params, cfg: ModelConfig, batch: dict, *,
                  remat: bool = True, q_chunk: int = 512, moe_cf=1.25,
                  return_hidden: bool = False
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full (causal) forward for training. Returns (logits, moe-aux), or
    (hidden, moe-aux) with ``return_hidden`` (chunked-CE path)."""
    tokens = batch["tokens"]
    if cfg.family == "ssm":
        return ssm_lm.ssm_lm_forward(params, cfg, tokens, remat=remat,
                                     return_hidden=return_hidden)
    if cfg.family == "hybrid":
        return hybrid.hybrid_forward(params, cfg, tokens, remat=remat,
                                     q_chunk=q_chunk,
                                     return_hidden=return_hidden)
    if cfg.family == "encdec":
        return encdec.encdec_forward(params, cfg, tokens,
                                     src_embeds=batch["src_embeds"],
                                     remat=remat, q_chunk=q_chunk,
                                     return_hidden=return_hidden)
    return transformer.lm_forward(
        params, cfg, tokens,
        positions=batch.get("positions"),
        extra_embeds=batch.get("vision_embeds"),
        remat=remat, q_chunk=q_chunk, moe_cf=moe_cf,
        return_hidden=return_hidden)


def model_prefill(params: Params, cfg: ModelConfig, batch: dict,
                  cache_len: int, *, q_chunk: int = 512, moe_cf=1.25
                  ) -> Tuple[jnp.ndarray, Params]:
    tokens = batch["tokens"]
    if cfg.family == "ssm":
        return ssm_lm.ssm_lm_prefill(params, cfg, tokens)
    if cfg.family == "hybrid":
        return hybrid.hybrid_prefill(params, cfg, tokens, cache_len,
                                     q_chunk=q_chunk)
    if cfg.family == "encdec":
        return encdec.encdec_prefill(params, cfg, tokens, cache_len,
                                     src_embeds=batch["src_embeds"],
                                     q_chunk=q_chunk)
    return transformer.lm_prefill(
        params, cfg, tokens, cache_len,
        positions=batch.get("positions"),
        extra_embeds=batch.get("vision_embeds"),
        q_chunk=q_chunk, moe_cf=moe_cf)


def model_decode(params: Params, cfg: ModelConfig, token: jnp.ndarray,
                 cache: Params, pos, *,
                 positions: Optional[jnp.ndarray] = None, moe_cf=None
                 ) -> Tuple[jnp.ndarray, Params]:
    if cfg.family == "ssm":
        return ssm_lm.ssm_lm_decode(params, cfg, token, cache, pos)
    if cfg.family == "hybrid":
        return hybrid.hybrid_decode(params, cfg, token, cache, pos)
    if cfg.family == "encdec":
        return encdec.encdec_decode(params, cfg, token, cache, pos)
    return transformer.lm_decode(params, cfg, token, cache, pos,
                                 positions=positions, moe_cf=moe_cf)


def init_cache(cfg: ModelConfig, batch: int, cache_len: int,
               dtype=None) -> Params:
    if cfg.family == "ssm":
        return ssm_lm.init_ssm_cache(cfg, batch, cache_len, dtype)
    if cfg.family == "hybrid":
        return hybrid.init_hybrid_cache(cfg, batch, cache_len, dtype)
    if cfg.family == "encdec":
        return encdec.init_encdec_cache(cfg, batch, cache_len, dtype=dtype)
    return transformer.init_lm_cache(cfg, batch, cache_len, dtype)
