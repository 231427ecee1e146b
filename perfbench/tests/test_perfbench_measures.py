"""The harness's arithmetic on hand-made records: percentiles, due-time
accounting (a failed request counts as missing) and rates."""
import math

from perfbench.loadgen import Record
from perfbench.measures import Run
from perfbench.stats import percentile
from perfbench.traffic import TrafficRequest

CFG = {"num_layers": 1, "d_model": 4, "num_heads": 2, "num_kv_heads": 1,
       "head_dim": 2, "d_ff": 8, "vocab_size": 300}


def rec(due, tokens, finish="length", prompt="abcd", cached=0):
    r = Record(req=TrafficRequest(0, due, prompt, len(tokens), "q"),
               due_t=due)
    r.token_t = list(tokens)
    r.tokens = [1] * len(tokens)
    r.finish = finish
    r.prompt_tokens = len(prompt)
    r.cached_tokens = cached
    return r


def test_percentile_nearest_rank():
    xs = list(range(1, 11))                       # 1..10
    assert percentile(xs, 50) == 5
    assert percentile(xs, 90) == 9
    assert percentile(xs, 95) == 10
    assert percentile([3.0], 99) == 3.0
    assert percentile([], 50) is None
    assert percentile([1.0, math.inf], 90) == math.inf


def test_due_time_accounting_counts_failures_as_missing():
    recs = [rec(0.0, [0.5, 0.6]),
            rec(1.0, [1.2, 1.3]),
            rec(2.0, [], finish="shed"),          # refused: missing
            rec(3.0, []),                         # never served: missing
            rec(20.0, [20.1, 20.3])]              # due after the window
    run = Run(cfg=CFG, records=recs, t0=0.0, window_s=10.0, end_t=12.0,
              open_loop=True, setup_s=1.0)
    assert run.attempted() == 4
    assert run.failed() == 2
    assert [round(g, 9) for g in run.itls()] == [0.1, 0.1]


def test_backlog_queued_requests_are_not_failures_and_rate_is_per_window():
    recs = [rec(0.0, [0.5 + 0.1 * i for i in range(20)]),   # 0.5 .. 2.4
            rec(0.0, [], finish="cancelled")]
    run = Run(cfg=CFG, records=recs, t0=0.0, window_s=2.0, end_t=2.0,
              open_loop=False, setup_s=1.0)
    assert run.failed() == 0
    assert run.tokens_in_window() == 15                      # 0.5 .. 1.9
    n, work = run.decode_work()
    assert n == 14                          # the first token is prefill's
    # token j attends prompt (4) + j positions
    assert work.flops == sum(4.0 * 2 * 2 * (4 + j) * 1 for j in range(1, 15))


def test_prefix_hit_share_and_step_time_readers():
    from perfbench import spec
    recs = [rec(0.0, [0.1], prompt="a" * 10, cached=6),
            rec(0.5, [0.7], prompt="b" * 30, cached=0)]
    run = Run(cfg=CFG, records=recs, t0=0.0, window_s=2.0, end_t=2.0,
              open_loop=True, setup_s=1.0, engine_steps=40)
    bench = spec.Benchmark.load(spec.HERE + "/..")
    assert bench.reader("prefix_hit_share")(run) == 100.0 * 6 / 40
    assert bench.reader("step_ms.serve")(run) == 50.0
    assert bench.reader("itl_p50_s")(run) is None        # one token each
    assert bench.reader("paged_decode_roofline.serve")(run) is None
