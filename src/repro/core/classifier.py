"""Prompt-complexity classifier — the paper's DistilBERT analogue, in JAX.

A small bidirectional transformer encoder over byte tokens with a [CLS]
head (paper Eq. 3–4):

    p_k = softmax(W h_[CLS] + b),   C_hat = argmax_k p_k

Trained exactly as the paper describes where transferable: 3-way
cross-entropy, AdamW, batch 32, lr 2e-5 (epochs scaled down for CPU).
The paper fine-tunes a 66M-param pretrained DistilBERT; with no weights
available offline we train a compact encoder from scratch on the same
corpus both routers share — the fair-comparison requirement the paper
states. Validation accuracy is reported as measured (paper: 96.8%).
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.benchmarks import TIERS, Prompt
from repro.data.tokenizer import ByteTokenizer
from repro.models.common import (dense_init, embed_init, init_layernorm,
                                 layernorm, stack_init)
from repro.training.optimizer import AdamWConfig, adamw_update, init_adamw

CLS_ID = 259  # reuse SEP slot as [CLS]


@dataclass(frozen=True)
class ClassifierConfig:
    vocab_size: int = 260
    max_len: int = 128
    d_model: int = 128
    num_heads: int = 4
    d_ff: int = 512
    num_layers: int = 2
    num_classes: int = 3


def init_classifier(cfg: ClassifierConfig, key) -> dict:
    ks = jax.random.split(key, 5)

    def block(k):
        k1, k2, k3, k4 = jax.random.split(k, 4)
        return {
            "ln1": init_layernorm(cfg.d_model),
            "wqkv": dense_init(k1, cfg.d_model, 3 * cfg.d_model),
            "wo": dense_init(k2, cfg.d_model, cfg.d_model),
            "ln2": init_layernorm(cfg.d_model),
            "w1": dense_init(k3, cfg.d_model, cfg.d_ff),
            "w2": dense_init(k4, cfg.d_ff, cfg.d_model),
        }

    return {
        "embed": embed_init(ks[0], cfg.vocab_size, cfg.d_model),
        "pos": embed_init(ks[1], cfg.max_len, cfg.d_model),
        "layers": stack_init(ks[2], cfg.num_layers, block),
        "ln_f": init_layernorm(cfg.d_model),
        "w_cls": dense_init(ks[3], cfg.d_model, cfg.num_classes),
        "b_cls": jnp.zeros((cfg.num_classes,)),
    }


def classifier_logits(params: dict, cfg: ClassifierConfig,
                      tokens: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """tokens: (B, S) int32 with [CLS] at position 0; mask: (B, S) {0,1}."""
    B, S = tokens.shape
    h = params["embed"][tokens] + params["pos"][None, :S]
    neg = jnp.where(mask[:, None, None, :] > 0, 0.0, -1e30)

    def body(h, lp):
        x = layernorm(lp["ln1"], h)
        qkv = x @ lp["wqkv"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        hd = cfg.d_model // cfg.num_heads
        q = q.reshape(B, S, cfg.num_heads, hd).transpose(0, 2, 1, 3)
        k = k.reshape(B, S, cfg.num_heads, hd).transpose(0, 2, 1, 3)
        v = v.reshape(B, S, cfg.num_heads, hd).transpose(0, 2, 1, 3)
        s = (q @ k.transpose(0, 1, 3, 2)) / np.sqrt(hd) + neg
        a = jax.nn.softmax(s, axis=-1) @ v
        a = a.transpose(0, 2, 1, 3).reshape(B, S, cfg.d_model)
        h = h + a @ lp["wo"]
        x = layernorm(lp["ln2"], h)
        h = h + jax.nn.gelu(x @ lp["w1"]) @ lp["w2"]
        return h, None

    h, _ = jax.lax.scan(body, h, params["layers"])
    h_cls = layernorm(params["ln_f"], h[:, 0])     # [CLS] embedding (Eq. 3)
    return h_cls @ params["w_cls"] + params["b_cls"]


# ---------------------------------------------------------------------------
# data prep + training


def encode_prompts(texts: Sequence[str], max_len: int = 128
                   ) -> Tuple[np.ndarray, np.ndarray]:
    tok = ByteTokenizer()
    ids = np.full((len(texts), max_len), 0, np.int32)
    mask = np.zeros((len(texts), max_len), np.int32)
    for i, t in enumerate(texts):
        e = [CLS_ID] + tok.encode(t)[: max_len - 1]
        ids[i, : len(e)] = e
        mask[i, : len(e)] = 1
    return ids, mask


def train_classifier(
    prompts: List[Prompt],
    val_prompts: List[Prompt],
    cfg: ClassifierConfig = ClassifierConfig(),
    epochs: int = 3,
    batch_size: int = 32,           # paper hyperparameter
    lr: float = 2e-5 * 50,          # paper lr is for a pretrained 66M model;
                                    # scaled for from-scratch training
    seed: int = 0,
    log=print,
) -> Tuple[dict, dict]:
    """Returns (params, report{val_accuracy, ...})."""
    x, m = encode_prompts([p.text for p in prompts], cfg.max_len)
    y = np.asarray([TIERS.index(p.complexity) for p in prompts], np.int32)
    xv, mv = encode_prompts([p.text for p in val_prompts], cfg.max_len)
    yv = np.asarray([TIERS.index(p.complexity) for p in val_prompts], np.int32)

    params = init_classifier(cfg, jax.random.PRNGKey(seed))
    opt = AdamWConfig(lr=lr, weight_decay=0.01, grad_clip=1.0,
                      warmup_steps=20,
                      total_steps=max(1, epochs * len(prompts) // batch_size))
    opt_state = init_adamw(params)

    def loss_fn(params, tokens, mask, labels):
        logits = classifier_logits(params, cfg, tokens, mask)
        logp = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(logp, labels[:, None], axis=1).mean()
        acc = (jnp.argmax(logits, -1) == labels).mean()
        return nll, acc

    @jax.jit
    def step(params, opt_state, tokens, mask, labels):
        (nll, acc), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, tokens, mask, labels)
        params, opt_state, _ = adamw_update(opt, grads, opt_state, params)
        return params, opt_state, nll, acc

    @jax.jit
    def eval_logits(params, tokens, mask):
        return classifier_logits(params, cfg, tokens, mask)

    rng = np.random.RandomState(seed)
    n = len(prompts)
    t0 = time.perf_counter()
    for ep in range(epochs):
        order = rng.permutation(n)
        accs = []
        for i in range(0, n - batch_size + 1, batch_size):
            b = order[i:i + batch_size]
            params, opt_state, nll, acc = step(
                params, opt_state, jnp.asarray(x[b]), jnp.asarray(m[b]),
                jnp.asarray(y[b]))
            accs.append(float(acc))
        if log:
            log(f"classifier epoch {ep}: train_acc={np.mean(accs):.3f}")

    # validation
    preds = []
    for i in range(0, len(xv), 256):
        lg = eval_logits(params, jnp.asarray(xv[i:i + 256]),
                         jnp.asarray(mv[i:i + 256]))
        preds.append(np.argmax(np.asarray(lg), -1))
    preds = np.concatenate(preds) if preds else np.zeros(0, np.int64)
    val_acc = float((preds == yv).mean()) if len(yv) else 0.0
    report = {"val_accuracy": val_acc, "train_secs": time.perf_counter() - t0,
              "n_train": n, "n_val": len(yv), "epochs": epochs}
    if log:
        log(f"classifier val_accuracy={val_acc:.3f} (paper: 0.968)")
    return params, report


@functools.partial(jax.jit, static_argnums=(1,))
def _proba(params: dict, cfg: ClassifierConfig, tokens, mask):
    # one jitted function for every call: a per-call jit would compile
    # again on each routed request
    return jax.nn.softmax(classifier_logits(params, cfg, tokens, mask), -1)


def predict_proba(params: dict, cfg: ClassifierConfig,
                  texts: Sequence[str]) -> np.ndarray:
    x, m = encode_prompts(texts, cfg.max_len)
    out = []
    for i in range(0, len(x), 256):
        out.append(np.asarray(_proba(params, cfg, jnp.asarray(x[i:i + 256]),
                                     jnp.asarray(m[i:i + 256]))))
    return np.concatenate(out) if out else np.zeros((0, cfg.num_classes))
