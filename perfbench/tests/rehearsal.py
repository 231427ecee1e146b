"""A cell run in-process at a small size on the CPU, through the
harness's own ``run``: everything but the look for a chip."""
import os
import sys

from perfbench import harness, spec

sys.path.insert(0, os.path.join(os.path.dirname(spec.HERE), "src"))

TINY = {"num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 2,
        "head_dim": 16, "d_ff": 128, "vocab_size": 512,
        "serve": {"backend": "vllm", "max_seq": 384, "replicas": 1,
                  "router": "keyword"},
        "correct": {"mean_logit_gap_limit": 0.01}}
MIX = {"arrival": {"process": "poisson", "rate_per_s": 3.0},
       "classes": [{"name": "question", "share": 1.0,
                    "prompt": {"source": "corpus"},
                    "output": {"source": "uniform", "min_tokens": 3,
                               "max_tokens": 8}}]}


def rehearse(monkeypatch, workload="smollm360m-zeroshot", mode="reference",
             hook=None, seconds=2.0, controls=()):
    from repro.kernels import ops
    monkeypatch.setattr(harness, "use_cache_dir", lambda: None)
    args = harness.parse(["--workload", workload, "--seed", str(2 ** 31 + 3),
                          "--seconds", str(seconds), "--trace", "0"])
    import time
    with ops.kernel_dispatch(mode):
        return harness.run(args, time.perf_counter(), require_tpu=False,
                           overrides=TINY, mix=MIX, hook=hook,
                           controls=controls)
