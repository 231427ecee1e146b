"""Smoke run of the paged serve path on one TPU at a published model width.

    python chip_smoke.py

One process runs four phases in order; the first failure ends the run
with a non-zero exit code:

  (a) device   place the persistent compile cache, print the devices, and
               stop unless JAX sees a TPU and kernel dispatch resolves to
               the Mosaic-compiled Pallas kernels;
  (b) kernels  paged decode and paged chunk-prefill attention at the
               model's head geometry in bf16, against the
               ``kernels/ref.py`` oracles;
  (c) serve    a ``ServeFrontend`` over the paged engine serves a few
               requests routed by the hybrid keyword + classifier router:
               several prefill buckets, a chunked prefill that attends
               cached blocks, a two-turn session served from the radix
               prefix cache, and one streamed request;
  (d) logits   prefill-then-decode logits of one prompt under the Mosaic
               kernels against the jnp reference trunk.

The model is smollm-360m at its published width (32 layers, d_model 960,
GQA 15/5, head_dim 64, vocab 49152, bf16) with random weights from a
seed; nothing is downloaded. Every time printed is a single-run smoke
figure with compilation included, not a benchmark number. The last line
of stdout is a JSON object naming the device.
"""
from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.configs.registry import ARCHS  # noqa: E402
from repro.core.classifier import ClassifierConfig, train_classifier  # noqa: E402
from repro.core.gateway import GatewayConfig, ServeFrontend  # noqa: E402
from repro.core.router import HybridRouter, SemanticRouter  # noqa: E402
from repro.data.benchmarks import generate_corpus  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.serve import build_models, use_compile_cache  # noqa: E402
from repro.models import init_model  # noqa: E402
from repro.models.transformer import init_paged_cache  # noqa: E402
from repro.serving import get_backend  # noqa: E402
from repro.serving.engine import (DEFAULT_BLOCK_SIZE,  # noqa: E402
                                  InferenceEngine, compile_paged_fns)

ARCH = "smollm-360m"
MAX_SEQ = 2048
BACKEND = "trt"            # the frontend's default backend column
CHUNK = 64                 # prefill chunk bound (the frontend's default)
SEED = 0
# bf16 kernel tolerance, the one tests/test_kernels.py holds the kernels
# to: both sides compute in f32 from the same bf16 operands and round the
# output to bf16, so they differ by about one bf16 ulp (2**-8 relative)
KERNEL_TOL = 2e-2
# logits tolerance, relative to the largest reference logit: the two
# trunks differ only in summation order inside attention, which flips
# single bf16 roundings of an attention output (2**-8 relative); across
# the layers those flips add up to a few bf16 ulps of the hidden state,
# and the logits are an f32 product of that hidden state
LOGIT_TOL = 5e-2


class SmokeFailure(RuntimeError):
    """A phase found the system not working; the run stops."""


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# (a) device


def phase_device() -> dict:
    """The device JAX runs on; fails unless it is a TPU driven through the
    Mosaic kernels."""
    devices = jax.devices()
    print(f"[a] devices: {devices}")
    d = devices[0]
    check(d.platform == "tpu",
          f"no TPU: JAX sees {d.platform!r} devices only")
    check(ops.kernel_mode() == "mosaic",
          f"kernel dispatch is {ops.kernel_mode()!r}, not 'mosaic'")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


# ---------------------------------------------------------------------------
# (b) kernels


def _run_kernel(name, fn, args, oracle, interpret: bool) -> None:
    t0 = time.perf_counter()
    compiled = fn.lower(*args, interpret=interpret).compile()
    t1 = time.perf_counter()
    out = np.asarray(jax.block_until_ready(compiled(*args)), np.float32)
    t2 = time.perf_counter()
    want = np.asarray(oracle(*args), np.float32)
    if name.startswith("prefill"):      # pad rows are garbage by contract
        s_real = int(args[-1])
        out, want = out[:s_real], want[:s_real]
    err = np.abs(out - want)
    bad = int(np.sum(err > KERNEL_TOL + KERNEL_TOL * np.abs(want)))
    print(f"[b] {name:34s} shape={out.shape} max_abs_err={err.max():.3e} "
          f"compile={t1 - t0:.3f}s run={t2 - t1:.4f}s")
    check(np.all(np.isfinite(out)), f"{name}: non-finite output")
    check(bad == 0, f"{name}: {bad} elements outside the bf16 tolerance "
                    f"{KERNEL_TOL} (max abs err {err.max():.3e})")


def phase_kernels(cfg, max_seq: int, batch: int, interpret: bool) -> None:
    """Both paged kernels at ``cfg``'s head geometry in bf16, with block
    tables as long as ``max_seq`` and a pool of ``batch`` full sequences."""
    hq, hkv, d, bs = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, \
        DEFAULT_BLOCK_SIZE
    nbseq = max_seq // bs
    nb = batch * nbseq
    rng = np.random.RandomState(SEED)

    def rand(*shape):
        return jnp.asarray(rng.standard_normal(shape).astype(np.float32),
                           jnp.bfloat16)

    i32 = jnp.int32
    kp, vp = rand(nb, hkv, bs, d), rand(nb, hkv, bs, d)
    tables = np.stack([rng.permutation(nb)[:nbseq] for _ in range(batch)])
    lens = rng.randint(1, max_seq + 1, size=batch)
    lens[0] = max_seq                            # one full-length sequence
    _run_kernel("decode", ops.paged_decode_attention,
                (rand(batch, hq, d), kp, vp, jnp.asarray(tables, i32),
                 jnp.asarray(lens, i32)),
                ref.ref_paged_decode_attention, interpret)

    sb = CHUNK
    # as the engine calls it: the gathered context as one block
    ctx = max_seq
    _run_kernel("prefill, context as one block", ops.paged_prefill_attention,
                (rand(sb, hq, d), rand(1, hkv, ctx, d), rand(1, hkv, ctx, d),
                 rand(hkv, sb, d), rand(hkv, sb, d), jnp.zeros((1,), i32),
                 jnp.int32(ctx - sb - 7), jnp.int32(sb - 3)),
                ref.ref_paged_prefill_attention, interpret)
    # through a block table of the pool, context ending mid-block
    nbctx = 8
    _run_kernel("prefill, context through the table",
                ops.paged_prefill_attention,
                (rand(sb, hq, d), kp, vp, rand(hkv, sb, d), rand(hkv, sb, d),
                 jnp.asarray(tables[1, :nbctx], i32),
                 jnp.int32(nbctx * bs - 5), jnp.int32(sb)),
                ref.ref_paged_prefill_attention, interpret)


# ---------------------------------------------------------------------------
# (c) serve


def train_router(n_prompts: int = 384, epochs: int = 1):
    """The paper's hybrid router, its classifier trained here from the
    seeded corpus (nothing is loaded from disk)."""
    corpus = generate_corpus(n_prompts, seed=SEED)
    order = np.random.RandomState(SEED).permutation(len(corpus))
    n_val = len(corpus) // 8
    val = [corpus[i] for i in order[:n_val]]
    train = [corpus[i] for i in order[n_val:]]
    ccfg = ClassifierConfig()
    params, report = train_classifier(train, val, ccfg, epochs=epochs,
                                      seed=SEED, log=None)
    return HybridRouter(SemanticRouter(params, ccfg)), report


def _texts(n_tokens):
    """Deterministic ASCII prompts, one byte (= one token) per character."""
    corpus = generate_corpus(64, seed=SEED + 1)
    words = " ".join(p.text for p in corpus).encode("ascii", "ignore")
    out, at = [], 0
    for n in n_tokens:
        out.append(words[at:at + n].decode())
        at += n
    return out


def phase_serve(name: str, cfg, max_seq: int) -> None:
    """A few requests through the public frontend on the paged engine,
    serving ``cfg`` under its registry ``name``."""
    t0 = time.perf_counter()
    router, report = train_router()
    print(f"[c] router: hybrid, classifier trained on {report['n_train']} "
          f"prompts for {report['epochs']} epoch "
          f"(val accuracy {report['val_accuracy']:.3f}) "
          f"in {time.perf_counter() - t0:.2f}s")
    fe = ServeFrontend(GatewayConfig(
        models={name: cfg}, router=router, backends=(BACKEND,),
        paged=True, max_seq=max_seq, chunk_tokens=CHUNK, autoscale=False))
    # (label, prompt tokens, max_new_tokens): prefill buckets 8, 16, 32
    # and 64, and a prompt of three chunks whose later chunks attend the
    # blocks the earlier ones cached
    batch = [("bucket-8", 6, 8), ("bucket-16", 12, 8), ("bucket-32", 28, 8),
             ("chunked", 150, 8), ("bucket-64", 40, 16)]
    turns = [("turn-1", 40, 8), ("turn-2", 24, 8)]
    stream = ("streamed", 20, 12)
    specs = batch + turns + [stream]
    texts = dict(zip([s[0] for s in specs], _texts([s[1] for s in specs])))
    print(f"[c] {len(specs)} requests, max_seq {max_seq}, chunk {CHUNK}; "
          f"TTFT and latency below are single-run smoke figures and "
          f"include first-use compilation of each prefill shape")
    print("[c] int8 KV pools are not covered: they take the jnp reference "
          "path, not the kernels (models/attention.py _kernel_dispatch)")

    results = []
    handles = [(s, fe.submit(texts[s[0]], max_new_tokens=s[2]))
               for s in batch]
    results += [(s, h.result()) for s, h in handles]
    for s in turns:
        results.append((s, fe.submit(texts[s[0]], max_new_tokens=s[2],
                                     session_id="chat").result()))
    h = fe.submit(texts[stream[0]], max_new_tokens=stream[2])
    streamed = [e.token for e in h.tokens() if e.kind == "token"]
    results.append((stream, h.response))

    engines = fe.pool.paged_replicas(name, BACKEND)
    check(len(engines) == 1, f"expected one paged replica, found "
                             f"{len(engines)}")
    for label, secs in fe.cold_starts:
        print(f"[c] replica start {label}: {secs:.2f}s "
              f"(param init + compile + probe request)")
    for (label, n, m), r in results:
        u = r.usage
        print(f"[c] {label:9s} prompt={u.prompt_tokens:4d} "
              f"cached={u.cached_tokens:3d} chunks={u.prefill_chunks} "
              f"tokens={u.completion_tokens:2d} finish={r.finish_reason:6s} "
              f"ttft={r.ttft_s:.3f}s latency={r.latency_s:.3f}s")
        check(r.model == name, f"{label}: routed to {r.model}")
        check(r.finish_reason in ("stop", "length"),
              f"{label}: finished {r.finish_reason!r}")
        check(len(r.new_tokens) == m and u.completion_tokens == m,
              f"{label}: {len(r.new_tokens)} tokens, expected {m}")
        if label != "turn-2":
            check(u.prompt_tokens == n,
                  f"{label}: prompt of {u.prompt_tokens} tokens, "
                  f"expected {n}")
    by = {s[0]: r for s, r in results}
    check(by["chunked"].usage.prefill_chunks >= 3,
          "the long prompt was not prefilled in chunks")
    check(by["turn-2"].usage.cached_tokens > 0,
          "turn 2 was not served from the prefix cache")
    check(streamed == by["streamed"].new_tokens,
          "streamed tokens differ from the response")
    print(f"[c] phase wall {time.perf_counter() - t0:.2f}s")


# ---------------------------------------------------------------------------
# (d) logits


def _prefill_decode_logits(cfg, params, max_seq, tokens, prompt_len, mode):
    """Logits of the last prompt token, then of each teacher-forced decode
    step, through the paged engine's compiled functions under ``mode``."""
    bs = DEFAULT_BLOCK_SIZE
    nbseq = max_seq // bs
    table = jnp.arange(nbseq, dtype=jnp.int32)
    with ops.kernel_dispatch(mode):               # read at trace time
        fns = compile_paged_fns(cfg, get_backend(BACKEND), max_seq, bs)
        cache = init_paged_cache(cfg, nbseq, bs, jnp.bfloat16)
        for start in range(0, prompt_len, CHUNK):
            n = min(CHUNK, prompt_len - start)
            padded = np.zeros((1, InferenceEngine._bucket_up(n)), np.int32)
            padded[0, :n] = tokens[start:start + n]
            ctx_kv = fns.gather(cache, table)
            logits, new_kv = fns.prefill(params, jnp.asarray(padded), ctx_kv,
                                         jnp.int32(start), jnp.int32(n))
            cache = fns.scatter(cache, new_kv, table, jnp.int32(start),
                                jnp.int32(n))
        out = [logits]
        for p in range(prompt_len, len(tokens)):
            logits, cache = fns.decode(
                params, jnp.asarray([[tokens[p]]], jnp.int32), cache,
                table[None], jnp.asarray([p], jnp.int32))
            out.append(logits)
    return np.concatenate([np.asarray(x, np.float32) for x in out])


def phase_logits(cfg, max_seq: int, mode: str, prompt_len: int = 100,
                 steps: int = 3) -> float:
    """Kernel trunk (``mode``) against the reference trunk on one prompt;
    returns the max logit error relative to the largest reference logit."""
    t0 = time.perf_counter()
    params = init_model(cfg, jax.random.PRNGKey(SEED))
    tokens = np.random.RandomState(SEED + 2).randint(
        0, cfg.vocab_size, prompt_len + steps)
    t1 = time.perf_counter()
    want = _prefill_decode_logits(cfg, params, max_seq, tokens, prompt_len,
                                  "reference")
    t2 = time.perf_counter()
    got = _prefill_decode_logits(cfg, params, max_seq, tokens, prompt_len,
                                 mode)
    print(f"[d] param init {t1 - t0:.2f}s; reference trunk {t2 - t1:.2f}s, "
          f"{mode} trunk {time.perf_counter() - t2:.2f}s (each compiles "
          f"its gather, prefill, scatter and decode on first use)")
    check(np.all(np.isfinite(got)), f"{mode}: non-finite logits")
    rel = float(np.abs(got - want).max() / np.abs(want).max())
    agree = int(np.sum(got.argmax(-1) == want.argmax(-1)))
    print(f"[d] {mode} vs reference, {prompt_len}-token prompt + {steps} "
          f"decode steps: max |dlogit| / max |logit| = {rel:.3e} "
          f"(tolerance {LOGIT_TOL}), argmax agrees at {agree}/{len(want)} "
          f"positions, wall {time.perf_counter() - t0:.2f}s")
    check(rel <= LOGIT_TOL, f"{mode} logits differ from the reference by "
                            f"{rel:.3e} of the logit scale")
    return rel


def main() -> int:
    t0 = time.perf_counter()
    print(f"[a] compile cache: {use_compile_cache()}")
    try:
        device = phase_device()
        cfg = build_models(ARCH, published=True)[ARCH]
        t = time.perf_counter()
        phase_kernels(cfg, MAX_SEQ, get_backend(BACKEND).max_batch,
                      interpret=False)
        print(f"[b] phase wall {time.perf_counter() - t:.2f}s")
        phase_serve(ARCH, cfg, MAX_SEQ)
        phase_logits(cfg, MAX_SEQ, "mosaic")
    except SmokeFailure as e:           # ends the run: no later phase runs
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"all phases passed in {time.perf_counter() - t0:.2f}s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
