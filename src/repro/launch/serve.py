"""Serving launcher: run the full Pick-and-Spin gateway on this host.

Spins a model pool (reduced float32 variants by default, for the CPU;
``--published`` serves the registry's configs at their published widths
and dtype, for the TPU), routes a synthetic request stream, and prints
per-model serving stats + lifecycle events.

Both planes speak serving API v2 (``repro.api``): typed
``CompletionRequest`` in, ``CompletionResponse`` out, shed requests as
structured results.
  * default      — serial ``Gateway`` facade: one blocking request at a
                   time (baseline; each request served to completion).
  * --concurrent — ``ServeFrontend``: open-loop Poisson arrivals
                   (--rate rps) into priority-ordered bounded queues,
                   many requests in flight across replica pools of real
                   engines, with the Algorithm-1 Spin loop ticking live
                   (scale-up under load, scale-to-zero when idle).

Usage:
  # serial baseline
  PYTHONPATH=src python -m repro.launch.serve --pool smollm-360m,glm4-9b \
      --requests 32 --profile balanced --router hybrid
  # concurrent serve plane
  PYTHONPATH=src python -m repro.launch.serve --concurrent --rate 8 \
      --pool smollm-360m,glm4-9b --requests 32
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time
from pathlib import Path
from typing import Dict

import jax
import numpy as np

from repro.api import CompletionRequest
from repro.configs.base import ModelConfig
from repro.configs.registry import ARCHS
from repro.core.gateway import Gateway, ServeFrontend
from repro.core.orchestrator import SpinConfig
from repro.core.router import KeywordRouter
from repro.core.scoring import PROFILES
from repro.obs import write_metrics_dump
from repro.serving import SchedulerConfig
from repro.data.benchmarks import generate_corpus

DEFAULT_POOL = "smollm-360m,phi3-medium-14b,command-r-plus-104b"
# sequence capacity of the reduced CPU configs: short synthetic prompts
SMOKE_MAX_SEQ = 96
CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Place JAX's persistent compilation cache; entry points call this
    once, before anything compiles. ``JAX_COMPILATION_CACHE_DIR``, when
    set, is honoured as is (JAX reads it itself). Otherwise the cache
    lives at a fixed ``<checkout>/.jax_cache`` — fixed because the path is
    part of what a later run must find again. Returns the directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def build_models(pool: str, published: bool = False
                 ) -> Dict[str, ModelConfig]:
    """Configs the serve plane executes, from a comma-separated list of
    registry names: ``reduced()`` float32 variants (the CPU default), or
    with ``published`` the registry configs at their published widths and
    dtype. Raises ValueError on an unknown name."""
    models = {}
    for name in pool.split(","):
        name = name.strip()
        if name not in ARCHS:
            raise ValueError(f"unknown arch {name!r}; choose from "
                             f"{sorted(ARCHS)}")
        models[name] = (ARCHS[name] if published else
                        dataclasses.replace(ARCHS[name].reduced(),
                                            dtype="float32"))
    return models


def build_router(kind: str):
    if kind == "keyword":
        return KeywordRouter()
    # semantic/hybrid need the classifier the benchmarks train (and cache
    # under benchmarks/artifacts/); the caller asked for it explicitly, so
    # a failure to get it is an error, not a silent keyword fallback
    sys.path.insert(0, str(CHECKOUT / "benchmarks"))
    from common import get_classifier
    sem, _ = get_classifier(log=None)
    if kind == "distilbert":
        return sem
    from repro.core.router import HybridRouter
    return HybridRouter(sem)


def _print_results(results, wall, args, mode):
    print(f"\nserved {len(results)} requests in {wall:.1f}s "
          f"({mode}, router={args.router}, profile={args.profile}, "
          f"tput={len(results) / max(wall, 1e-9):.2f} rps)")
    by_model = {}
    for r in results:
        by_model.setdefault((r.model, r.backend), []).append(r)
    print(f"{'service':30s} {'n':>4s} {'mean_ttft(s)':>12s} "
          f"{'mean_lat(s)':>11s} {'ok':>6s}")
    for (m, b), rs in sorted(by_model.items()):
        print(f"{m + '/' + b:30s} {len(rs):4d} "
              f"{np.mean([r.ttft_s for r in rs]):12.3f} "
              f"{np.mean([r.latency_s for r in rs]):11.3f} "
              f"{sum(r.completed for r in rs):3d}/{len(rs)}")


def _dump_metrics(frontend, path: str) -> None:
    """--metrics-dump: write the observability artifact set (Prometheus
    exposition + decision events + request spans) and print the tail
    quantiles the registry answers live."""
    obs = frontend.obs
    if not path or obs is None:
        return
    reg = obs.registry
    print("\nper-service latency quantiles (from the metrics registry):")
    for label in reg.labels("ttft_s"):
        p50 = reg.quantile("ttft_s", label, 0.5)
        p95 = reg.quantile("ttft_s", label, 0.95)
        print(f"  {label:22s} ttft p50={p50:.3f}s p95={p95:.3f}s  "
              f"itl p95={reg.quantile('itl_s', label, 0.95):.4f}s  "
              f"e2e p95={reg.quantile('e2e_s', label, 0.95):.3f}s")
    paths = write_metrics_dump(path, reg, events=obs.events,
                               tracer=obs.tracer)
    print("metrics dump: " + ", ".join(paths))
    for label in reg.labels("cost_per_query_usd"):
        print(f"  {label:22s} measured cost/query "
              f"${reg.value('cost_per_query_usd', label):.6f}  "
              f"(conservation err "
              f"{obs.ledger.conservation_error():.2%})")


def run_serial(pool, args) -> None:
    gw = Gateway(pool, router=build_router(args.router),
                 profile=PROFILES[args.profile], max_seq=args.max_seq)
    prompts = generate_corpus(max(args.requests, 64), seed=17)[: args.requests]

    t0 = time.perf_counter()
    results = [gw.handle(p.text, max_new_tokens=args.max_new_tokens,
                         deadline_s=args.deadline_s) for p in prompts]
    wall = time.perf_counter() - t0

    _print_results(results, wall, args, "serial")
    print("\nlifecycle events (cold/warm starts):")
    for name, secs in gw.cold_starts:
        print(f"  {name:40s} {secs:6.2f}s")
    _dump_metrics(gw.frontend, args.metrics_dump)


def run_concurrent(pool, args) -> None:
    spin = SpinConfig(window_s=60.0, cooldown_s=0.5, idle_tau_s=2.0,
                      tick_s=0.2, max_replicas=4)
    faults = None
    if args.chaos_rate > 0 or args.chaos_kill_step > 0:
        from repro.serving import FaultPlan, FaultSpec
        specs = []
        if args.chaos_kill_step > 0:
            specs.append(FaultSpec("step_error",
                                   at_step=args.chaos_kill_step, replica=0))
        if args.chaos_rate > 0:
            specs.append(FaultSpec("step_error", rate=args.chaos_rate))
        faults = FaultPlan(specs, seed=args.chaos_seed)
    gw = ServeFrontend(pool, router=build_router(args.router),
                       profile=PROFILES[args.profile],
                       max_seq=args.max_seq, spin=spin,
                       chunk_tokens=args.chunk_tokens or None,
                       step_token_budget=args.step_token_budget or None,
                       decode_burst=args.decode_burst,
                       spec_draft=args.spec_draft or None,
                       spec_k=args.spec_k,
                       flight_record=args.flight_record or None,
                       faults=faults,
                       sched=SchedulerConfig(
                           max_queue_depth=args.max_queue_depth))
    prompts = generate_corpus(max(args.requests, 64), seed=17)[: args.requests]
    rng = np.random.RandomState(3)
    arrivals = np.cumsum(rng.exponential(1.0 / args.rate, size=len(prompts)))
    reqs = [CompletionRequest(prompt=p.text,
                              max_new_tokens=args.max_new_tokens,
                              deadline_s=args.deadline_s) for p in prompts]

    handles, wall = gw.serve_open_loop(reqs, arrivals)
    gw.settle(timeout_s=spin.idle_tau_s + 1.0)
    results = [h.response for h in handles if not h.shed]

    _print_results(results, wall, args, f"concurrent @ {args.rate:.1f} rps")
    shed = sum(h.shed for h in handles)
    if shed:
        print(f"shed at admission (queue depth {args.max_queue_depth}): "
              f"{shed}")
    if faults is not None:
        retried = sum(r.usage.retries > 0 for r in results if r is not None)
        print(f"chaos: {len(faults.fired)} fault(s) fired, "
              f"{gw.pool.quarantines} quarantine(s), "
              f"{retried} request(s) recovered via retry")
    print("\nlifecycle events (pool, measured on live engines):")
    for e in gw.pool.events:
        print(f"  {e}")
    print("orchestrator decisions (Algorithm 1, live):")
    for e in gw.orch_events:
        print(f"  {e}")
    _dump_metrics(gw, args.metrics_dump)
    if args.flight_record and gw.obs is not None:
        # on-demand dump: the run's final step ring + event tail joins
        # whatever automatic anomaly dumps already landed in the file
        p = gw.obs.flight.dump("on-demand", t=time.perf_counter())
        print(f"flight record: {p} "
              f"({len(gw.obs.flight.dumps)} dump(s))")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--pool", default=DEFAULT_POOL)
    ap.add_argument("--published", action="store_true",
                    help="serve the registry configs at their published "
                         "widths and dtype instead of reduced float32 "
                         "variants (sized for an accelerator, not the CPU)")
    ap.add_argument("--max-seq", type=int, default=SMOKE_MAX_SEQ,
                    help="per-sequence token capacity (prompt + output)")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--profile", default="quality", choices=sorted(PROFILES))
    ap.add_argument("--router", default="keyword",
                    choices=("keyword", "distilbert", "hybrid"))
    ap.add_argument("--deadline-s", type=float, default=120.0)
    ap.add_argument("--concurrent", action="store_true",
                    help="use the ServeFrontend serve plane (replica pools, "
                         "bounded queues, live Spin control loop)")
    ap.add_argument("--rate", type=float, default=6.0,
                    help="open-loop Poisson arrival rate, rps (--concurrent)")
    ap.add_argument("--max-queue-depth", type=int, default=64,
                    help="per-service admission bound (--concurrent)")
    ap.add_argument("--chunk-tokens", type=int, default=64,
                    help="prefill chunk bound per engine step; 0 = "
                         "whole-prompt prefill (--concurrent)")
    ap.add_argument("--step-token-budget", type=int, default=256,
                    help="tokens one engine step may spend across decode "
                         "+ prefill; 0 = unbounded (--concurrent)")
    ap.add_argument("--decode-burst", type=int, default=1,
                    help="fused decode iterations per step when no "
                         "prefill backlog is pending (1 = stepwise; "
                         "throughput knob for offline traffic, bounds "
                         "cancel/deadline latency by K tokens) "
                         "(--concurrent)")
    ap.add_argument("--spec-draft", default="",
                    help="registry arch that speculatively drafts for "
                         "every engine it can co-reside with (vocab "
                         "match + KV headroom; others keep plain "
                         "stepwise decode) (--concurrent)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="drafted tokens per speculative verify step")
    ap.add_argument("--metrics-dump", default="",
                    help="write Prometheus exposition to PATH plus "
                         "PATH.events.jsonl (scale/shed/orch decisions) "
                         "and PATH.spans.jsonl (request lifecycles)")
    ap.add_argument("--chaos-rate", type=float, default=0.0,
                    help="per-step replica crash probability from a "
                         "seeded fault plan; failures are contained "
                         "(quarantine + deterministic retry) "
                         "(--concurrent)")
    ap.add_argument("--chaos-kill-step", type=int, default=0,
                    help="deterministically kill the first replica "
                         "incarnation at this engine step (0 = off) "
                         "(--concurrent)")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="seed for the fault plan's Bernoulli streams")
    ap.add_argument("--flight-record", default="",
                    help="flight-recorder JSONL sink: automatic anomaly "
                         "dumps (shed storm, expiry burst, engine "
                         "exception) plus one on-demand dump at exit "
                         "(--concurrent)")
    args = ap.parse_args()

    use_compile_cache()
    try:
        pool = build_models(args.pool, args.published)
    except ValueError as e:
        raise SystemExit(str(e)) from None

    if args.spec_draft and args.spec_draft not in ARCHS:
        raise SystemExit(f"unknown spec draft arch {args.spec_draft!r}; "
                         f"choose from {sorted(ARCHS)}")

    if args.concurrent:
        if args.rate <= 0:
            ap.error("--rate must be > 0 (open-loop arrivals per second)")
        run_concurrent(pool, args)
    else:
        run_serial(pool, args)


if __name__ == "__main__":
    main()
