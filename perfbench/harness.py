"""One run of one cell: set-up, a measured window, metrics, the check.

    python perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

Set-up builds the program's serve plane for the cell's configuration
(``ServeFrontend`` with the configuration's backend profile, ``max_seq``
and one replica, the keyword router, autoscale off, every other knob at
the program's default), warms up every shape the cell's traffic can
reach. The window then serves the traffic open loop (or drains a backlog) for
``--seconds``; nothing compiles inside it. With ``--trace 1`` the window
runs under the profiler and the per-layer metrics are printed instead of
the end-to-end ones. After the window, the program is freed and a plain
float32 reference re-scores a sample of the served tokens: that decides
``correct``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from perfbench import spec, traffic
from perfbench.loadgen import OpenLoop, Record

ROOT = os.path.dirname(spec.HERE)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
SAMPLE_TOKENS = 400        # served tokens the reference re-scores, at least
SAMPLE_MAX_SEQS = 8
DRAIN_CAP_S = 60.0         # longest wait past the window for first tokens
WARMUP_SEED = 7            # warm-up prompts are the same in every run


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


def parse(argv: Optional[List[str]] = None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def use_cache_dir() -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, for every program the run compiles."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoDevice(f"need {chips} TPU chip(s); JAX sees "
                       f"{len(devs)} {devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def memory_peak(chips: int) -> Optional[int]:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# ---------------------------------------------------------------------------
# set-up


def _letters(rng: np.random.Generator, n: int) -> str:
    return rng.integers(97, 123, size=n, dtype=np.uint8).tobytes().decode()


def _ctx_blocks(start: int, block: int = 16) -> int:
    """Power-of-two count of cache blocks a chunk at ``start`` reads."""
    c = 1
    while c * block < start:
        c *= 2
    return c


def warmup_phases(max_prompt: int, max_seq: int, chunk: int = 64,
                  block: int = 16) -> List[List[tuple]]:
    """Groups of (prompt, max_new_tokens), each served to completion in
    turn, that between them reach every compiled shape the traffic can:
    prefill chunks padded to each power-of-two size up to ``chunk`` at
    every power-of-two context the traffic's prompts reach, a first-token
    batch of each power-of-two size up to 16, the decode step, a prefix
    hit that ends mid-context and a whole-prompt hit (copy-on-write)."""
    rng = np.random.default_rng(WARMUP_SEED)
    tails = [b - b // 3 for b in (8, 16, 32, 64) if b <= chunk]
    top = _ctx_blocks(max(max_prompt - 1, 0), block)
    phases: List[List[tuple]] = [[(_letters(rng, 6), 2)]]
    for n in (2, 8, 16):                      # first-token batches
        phases.append([(_letters(rng, 6), 2) for _ in range(n)])
    seen = set()
    start = 0
    while start < max_seq:
        c = _ctx_blocks(start, block)
        if c > top:
            break
        if c not in seen:                     # tails at context c
            seen.add(c)
            phases.append([(_letters(rng, start + t), 1) for t in tails])
        start += chunk
    base = _letters(rng, 3 * block)           # prefix hits
    phases.append([(base, 2)])
    phases.append([(base[:2 * block] + _letters(rng, t), 1) for t in tails])
    phases.append([(base, 2)])
    return phases


def serve_all(fe, prompts: List[tuple]) -> None:
    from repro.api import CompletionRequest
    handles = [fe.submit(CompletionRequest(prompt=p, max_new_tokens=m))
               for p, m in prompts]
    while not all(h.done() for h in handles):
        fe.step()
    bad = [h.response.finish_reason for h in handles
           if h.response.finish_reason not in ("stop", "length")]
    if bad:
        raise RuntimeError(f"warm-up requests finished {bad}")


def build_frontend(cfg: dict, seed: int):
    """The program's serve plane for one configuration: only what
    defines the deployment is passed."""
    from repro.configs.base import ModelConfig
    from repro.core.gateway import GatewayConfig, ServeFrontend
    from repro.core.router import KeywordRouter
    import dataclasses
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    kw = {k: v for k, v in cfg.items() if k in fields}
    kw["name"] = cfg["served_as"]       # the program's name for the model
    mc = ModelConfig(**kw)
    srv = cfg["serve"]
    fe = ServeFrontend(GatewayConfig(
        models={mc.name: mc}, router=KeywordRouter(),
        backends=(srv["backend"],), max_seq=srv["max_seq"], seed=seed,
        autoscale=False))
    return fe, mc


def watch_compiles() -> list:
    """A list that gains an entry whenever JAX traces, compiles or loads
    a program from the persistent cache; none should inside the window."""
    import jax
    seen: list = []
    names = ("jaxpr_trace_duration", "backend_compile_duration",
             "cache_retrieval_time_sec")

    def on_event(name, secs, **_):
        if name.endswith(names):
            seen.append(name)
    jax.monitoring.register_event_duration_secs_listener(on_event)
    return seen


# ---------------------------------------------------------------------------
# correctness


def prompt_ids(text: str) -> List[int]:
    return list(text.encode("utf-8"))


def choose_sample(records: List[Record], seed: int) -> List[Record]:
    """Served requests for the reference to re-score: the one with the
    most served tokens, one of every traffic class, then others drawn
    from the seed until ``SAMPLE_TOKENS`` served tokens are in."""
    served = [r for r in records if r.tokens]
    if not served:
        return []
    chosen = [max(served, key=lambda r: len(r.tokens))]
    for cls in sorted({r.req.cls for r in served}):
        if all(r.req.cls != cls for r in chosen):
            chosen.append(max((r for r in served if r.req.cls == cls),
                              key=lambda r: len(r.tokens)))
    rng = np.random.default_rng(seed)
    for i in rng.permutation(len(served)):
        if (sum(len(r.tokens) for r in chosen) >= SAMPLE_TOKENS
                or len(chosen) >= SAMPLE_MAX_SEQS):
            break
        if served[i] not in chosen:
            chosen.append(served[i])
    return chosen


def gap_stats(gaps: np.ndarray) -> dict:
    """Mean and widest gap of served tokens, and the share that were not
    the reference's first choice."""
    return {"mean": float(np.mean(gaps)), "widest": float(np.max(gaps)),
            "not_first": float(np.mean(gaps > 0))}


def check(cfg: dict, records: List[Record], seed: int, prog_seed: int,
          controls=()) -> Tuple[Dict[str, dict], dict]:
    """The numbers compared, each with its limit, and what else the
    check read. ``controls`` (calibration only) puts each int8 control in
    the program's place on the same sample and records its readings, its
    own compared numbers and whether they pass."""
    length_errors = 0
    for r in records:
        if r.finish in ("stop", "length") and (
                r.finish != "length" or len(r.tokens) != r.req.max_new_tokens):
            length_errors += 1
        if r.tokens and r.prompt_tokens != len(prompt_ids(r.req.prompt)):
            length_errors += 1
    sample = choose_sample(records, seed)
    seqs = [(prompt_ids(r.req.prompt) + r.tokens, len(prompt_ids(r.req.prompt)))
            for r in sample]
    info = {"sample": [[len(t), n] for t, n in seqs]}
    got = []
    if sample:
        got = spec.reference(cfg).logit_gaps(cfg, prog_seed, seqs,
                                             controls=controls)
        info["program"] = gap_stats(np.concatenate([g["gap"] for g in got]))
    limit = cfg["correct"]["mean_logit_gap_limit"]

    def compare(mean):
        return {"mean_logit_gap": {"value": mean, "limit": limit},
                "length_errors": {"value": length_errors, "limit": 0}}
    for c in controls:
        # the control in the program's place: at every position of the
        # same prompts and served tokens it serves its own first choice
        stats = gap_stats(np.concatenate([g["control_gap." + c]
                                          for g in got])) if got else {}
        cc = compare(stats.get("mean"))
        info["control." + c] = {**stats, "correct": is_correct(cc),
                                "compared": cc}
    return compare(info["program"]["mean"] if got else None), info


def is_correct(compared: Dict[str, dict]) -> bool:
    gap = compared["mean_logit_gap"]
    return (gap["limit"] is not None and gap["value"] is not None
            and gap["value"] <= gap["limit"]
            and compared["length_errors"]["value"] <= 0)


# ---------------------------------------------------------------------------
# host pauses


class HostWatch:
    """Counts what can hold the host inside the window: garbage-collector
    passes (``gc.callbacks``) and prefix-cache evictions (each engine's
    ``evict``, timed as it passes through). Nothing is changed or
    suppressed. ``summary`` also says how much of the longest serve
    step each of them covers."""

    def __init__(self, fe, clock=time.perf_counter):
        self.clock = clock
        self.gc: List[tuple] = []          # (start, seconds, generation)
        self.evictions: List[tuple] = []   # (start, seconds, blocks freed)
        self._gc_t0 = None
        gc.callbacks.append(self._on_gc)
        for _, eng in fe.pool.engines():
            cache = getattr(eng, "prefix", None)
            if cache is not None:
                cache.evict = self._timed(cache.evict)

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = self.clock()
        elif self._gc_t0 is not None:
            self.gc.append((self._gc_t0, self.clock() - self._gc_t0,
                            info["generation"]))

    def _timed(self, evict):
        def timed(n):
            t = self.clock()
            freed = evict(n)
            self.evictions.append((t, self.clock() - t, freed))
            return freed
        return timed

    def stop(self) -> None:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def summary(self, steps: List[tuple], t0: float, t1: float) -> dict:
        """``steps``: (start, seconds) of every serve step the harness
        took; events are those that started inside [t0, t1]."""
        def within(evs):
            return [e for e in evs if t0 <= e[0] <= t1]

        def overlap(evs, a, b):
            return sum(max(0.0, min(b, s + d) - max(a, s))
                       for s, d, _ in evs)
        gcs, evs = within(self.gc), within(self.evictions)
        steps = [s for s in steps if t0 <= s[0] <= t1]
        out = {"steps": len(steps),
               "gc_passes": [sum(1 for e in gcs if e[2] == g)
                             for g in range(3)],
               "gc_s": sum(e[1] for e in gcs),
               "gc_max_s": max((e[1] for e in gcs), default=0.0),
               "evict_calls": len(evs),
               "evict_blocks": sum(e[2] for e in evs),
               "evict_s": sum(e[1] for e in evs),
               "evict_max_s": max((e[1] for e in evs), default=0.0)}
        if steps:
            a, d = max(steps, key=lambda s: s[1])
            out.update(step_max_s=d, step_max_at_s=a - t0,
                       gc_in_step_max_s=overlap(gcs, a, a + d),
                       evict_in_step_max_s=overlap(evs, a, a + d))
        return out


# ---------------------------------------------------------------------------
# the run


def run(args: argparse.Namespace, process_t0: float, *, require_tpu=True,
        bench: Optional[spec.Benchmark] = None,
        overrides: Optional[dict] = None, mix: Optional[dict] = None,
        hook: Optional[Callable[[object], None]] = None,
        controls=(), keep_trace: Optional[str] = None) -> dict:
    """One run; returns the result line's object. For rehearsals and
    calibration (never from the command line): ``overrides`` replaces
    configuration keys, ``mix`` the traffic mix, ``hook(frontend)`` is
    called once set-up is done, ``controls`` adds the int8 controls'
    readings, and ``keep_trace`` names a directory to copy the trace to."""
    bench = bench or spec.Benchmark.load(ROOT)
    cell = bench.cell(args.workload)
    cfg = bench.config(cell)
    if overrides:
        cfg = {**cfg, **overrides}
    mix = mix or bench.traffic(cell)
    use_cache_dir()
    import jax
    device = device_info(cell.chips, require_tpu)
    from perfbench.peaks import peaks_for
    peaks = peaks_for(device["kind"]) if require_tpu else None
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.api import CompletionRequest

    prog_seed = args.seed % (2 ** 32)
    reqs = traffic.generate(mix, args.seed, args.seconds)
    max_seq = cfg["serve"]["max_seq"]
    for r in reqs:
        if len(prompt_ids(r.prompt)) + r.max_new_tokens >= max_seq:
            raise ValueError(f"request {r.index} does not fit max_seq "
                             f"{max_seq}")
    fe, mc = build_frontend(cfg, prog_seed)
    for phase in warmup_phases(traffic.max_prompt_tokens(reqs), max_seq):
        serve_all(fe, phase)
    if hook is not None:
        hook(fe)
    steps_hist = fe.obs.registry.histogram("engine_step_s", mc.name)
    compiles = watch_compiles()
    host = HostWatch(fe)

    trace_dir = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        annotate = jax.profiler.TraceAnnotation
    else:
        annotate = None

    loop = OpenLoop(fe, lambda q: CompletionRequest(
        prompt=q.prompt, max_new_tokens=q.max_new_tokens), annotate=annotate)
    open_loop = mix["arrival"]["process"] != "backlog"
    window = {"steps0": steps_hist.count, "steps1": None, "ann": None}
    t0 = time.perf_counter()
    setup_s = t0 - process_t0
    t_end = t0 + args.seconds
    if annotate is not None:
        window["ann"] = annotate("harness.window")
        window["ann"].__enter__()

    def tick(now: float) -> None:
        if window["steps1"] is None and now >= t_end:
            window["steps1"] = steps_hist.count
            if window["ann"] is not None:
                window["ann"].__exit__(None, None, None)

    def until(now: float, records: List[Record]) -> bool:
        if now < t_end:
            return False
        if not open_loop or now >= t_end + DRAIN_CAP_S:
            return True
        return all(r.first_t is not None or r.finish
                   for r in records if r.due_t < t_end)

    compiles.clear()
    records = loop.run(reqs, t0, until, on_tick=tick)
    end_t = time.perf_counter()
    in_window = len(compiles)
    host.stop()
    tick(max(end_t, t_end))
    if args.trace:
        jax.profiler.stop_trace()
    loop.cancel_all()
    loop_steps = loop.step_times
    engine_steps = window["steps1"] - window["steps0"]
    mem_peak = memory_peak(cell.chips)
    del loop, fe, steps_hist
    gc.collect()

    summary = None
    if trace_dir is not None:
        from perfbench.tracereduce import reduce_dir
        summary = reduce_dir(trace_dir)
        if keep_trace:
            shutil.copytree(trace_dir, keep_trace, dirs_exist_ok=True)
        shutil.rmtree(trace_dir, ignore_errors=True)
    from perfbench.measures import Run
    data = Run(cfg=cfg, records=records, t0=t0, window_s=args.seconds,
               end_t=end_t, open_loop=open_loop, setup_s=setup_s,
               peaks=peaks, engine_steps=engine_steps,
               trace=summary)
    metrics = {}
    for m in (bench.per_layer_for(cell) if args.trace
              else bench.end_to_end_for(cell)):
        value = bench.reader(m.name)(data)
        if value is not None:
            metrics[m.name] = {"value": value, "unit": m.unit}
    device["memory_peak_bytes"] = mem_peak
    out = {"correct": False, "attempted": data.attempted(),
           "failed": data.failed(), "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = {"device_ops": summary.top_ops[:10],
                            "idle_gaps": summary.top_gaps[:10]}
    out["window"] = {"measured_s": end_t - t0, "requests": len(records),
                     "compiles_or_cache_loads": in_window,
                     "host": host.summary(loop_steps, t0, end_t)}
    t_check = time.perf_counter()
    compared, info = check(cfg, records, args.seed, prog_seed, controls)
    out["window"]["check_s"] = time.perf_counter() - t_check
    out["check"] = info
    out["correct"] = is_correct(compared)
    out["compared"] = compared
    return out


def main(argv: Optional[List[str]] = None, process_t0: float = 0.0) -> int:
    args = parse(argv)
    try:
        out = run(args, process_t0 or time.perf_counter())
    except NoDevice as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 3
    print("host " + json.dumps(out["window"]["host"]), file=sys.stderr)
    for name, c in out["compared"].items():
        print(f"compared {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(out))
    return 0
