"""Finds a cell's knee once: the highest open-loop rate whose backlog does
not grow over the window. One process, one set-up, one window per rate.

    python perfbench/tools/sweep.py --workload <cell> --seconds 30 --rates 1,1.5,2

For each rate it prints the requests due, the first tokens served, the
TTFT and ITL percentiles, and the mean number of requests waiting for
their first token in the second and the last quarter of the window: a
backlog that grows from one to the other means the rate is past the knee.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench import harness, spec, traffic  # noqa: E402
from perfbench.loadgen import OpenLoop  # noqa: E402
from perfbench.stats import percentile  # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seed", type=int, default=12345)
    a = p.parse_args()
    bench = spec.Benchmark.load(harness.ROOT)
    cell = bench.cell(a.workload)
    cfg, mix = bench.config(cell), bench.traffic(cell)
    harness.use_cache_dir()
    harness.device_info(cell.chips, True)
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    from repro.api import CompletionRequest
    rates = [float(r) for r in a.rates.split(",")]
    t = time.perf_counter()
    fe, _ = harness.build_frontend(cfg, a.seed)
    longest = 0
    for r in rates:
        m = dict(mix, arrival={"process": "poisson", "rate_per_s": r})
        longest = max(longest, traffic.max_prompt_tokens(
            traffic.generate(m, a.seed, a.seconds)))
    for phase in harness.warmup_phases(longest, cfg["serve"]["max_seq"]):
        harness.serve_all(fe, phase)
    print(json.dumps({"setup_s": time.perf_counter() - t}), flush=True)
    for r in rates:
        m = dict(mix, arrival={"process": "poisson", "rate_per_s": r})
        reqs = traffic.generate(m, a.seed, a.seconds)
        loop = OpenLoop(fe, lambda q: CompletionRequest(
            prompt=q.prompt, max_new_tokens=q.max_new_tokens))
        samples = []
        t0 = time.perf_counter()
        t_end = t0 + a.seconds

        def until(now, recs):
            waiting = sum(1 for x in recs if x.first_t is None and not x.finish)
            samples.append((now - t0, waiting))
            return now >= t_end

        recs = loop.run(reqs, t0, until)
        due = [x for x in recs if x.due_t < t_end]
        ttft = [x.first_t - x.due_t for x in due if x.first_t is not None]
        itl = [b - c for x in due for c, b in zip(x.token_t, x.token_t[1:])]

        def mean_q(lo, hi):
            q = [w for s, w in samples if lo * a.seconds <= s < hi * a.seconds]
            return sum(q) / len(q) if q else 0.0
        out = {"rate": r, "due": len(due), "first_tokens": len(ttft),
               "tokens": sum(len(x.token_t) for x in recs),
               "ttft_p50": percentile(ttft, 50), "ttft_p90": percentile(ttft, 90),
               "itl_p50": percentile(itl, 50), "itl_p95": percentile(itl, 95),
               "waiting_q2": mean_q(0.25, 0.5), "waiting_q4": mean_q(0.75, 1.0),
               "steps": len(loop.step_times)}
        print(json.dumps(out), flush=True)
        loop.cancel_all()
        while fe.has_work():
            fe.step()
    return 0


if __name__ == "__main__":
    sys.exit(main())
