"""Operations and bytes the served work needs, from per-request records.

Counted from what the requests asked for (prompt lengths, cached prefix
lengths, tokens decoded), never from a kernel's grid or padded shapes:
a kernel that stops touching empty cache slots shows as a gain, and a
share of the roofline computed from these counts cannot pass 100%
unless the kernel's time is read short. The model is a GQA decoder
stated by a configuration dict (the keys of ``configs/*.json``); the KV
cache, queries and attention outputs are bf16 (2 bytes).
"""
from __future__ import annotations

from dataclasses import dataclass

ACT_BYTES = 2                    # bf16 queries, outputs and KV entries


@dataclass(frozen=True)
class Work:
    flops: float = 0.0
    bytes: float = 0.0

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def least_seconds(self, peak_flops: float, peak_bytes_per_s: float
                      ) -> float:
        """Roofline time: the larger of compute time and memory time."""
        return max(self.flops / peak_flops, self.bytes / peak_bytes_per_s)


def matmul_weights(cfg: dict) -> int:
    """Weight elements one token multiplies through: every layer's Q, K,
    V and output projections and its gated FFN, and the output head."""
    d, hd = cfg["d_model"], cfg["head_dim"]
    attn = d * cfg["num_heads"] * hd + 2 * d * cfg["num_kv_heads"] * hd \
        + cfg["num_heads"] * hd * d
    ffn = 3 * d * cfg["d_ff"]
    return cfg["num_layers"] * (attn + ffn) + cfg["vocab_size"] * d


def decode_attention(cfg: dict, context: int) -> Work:
    """One decoded token attending ``context`` cached tokens (itself
    included), over all layers: QK^T and PV (2 FLOPs per multiply-add),
    and the least bytes: each K and V entry once, the query and the
    output once."""
    hq, hkv, hd, L = (cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"],
                      cfg["num_layers"])
    flops = 4.0 * hq * hd * context * L
    byts = (2.0 * context * hkv * hd + 2.0 * hq * hd) * ACT_BYTES * L
    return Work(flops, byts)


def prefill_attention(cfg: dict, cached: int, prompt: int) -> Work:
    """Causal attention of the uncached prompt tokens ``cached ..
    prompt-1``, each over every earlier position and itself, over all
    layers; the least bytes read each of the ``prompt`` K/V entries once
    and each uncached query and output once. Independent of how the
    prompt was cut into chunks."""
    hq, hkv, hd, L = (cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"],
                      cfg["num_layers"])
    n = prompt - cached
    if n <= 0:
        return Work()
    keys = (cached + 1 + prompt) * n / 2.0        # sum of t+1, t in [c, p)
    flops = 4.0 * hq * hd * keys * L
    byts = (2.0 * prompt * hkv * hd + 2.0 * n * hq * hd) * ACT_BYTES * L
    return Work(flops, byts)


def model_flops(cfg: dict, tokens: int, attention: Work) -> float:
    """Model FLOPs of ``tokens`` computed tokens: two per weight element
    per token, plus their attention's FLOPs."""
    return 2.0 * matmul_weights(cfg) * tokens + attention.flops
