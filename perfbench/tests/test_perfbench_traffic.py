"""The generator gives every seed the same work in another order."""
from collections import Counter

from perfbench import traffic

MIX = {"arrival": {"process": "poisson", "rate_per_s": 2.0},
       "classes": [
           {"name": "question", "share": 0.85, "prompt": {"source": "corpus"},
            "output": {"source": "corpus"}},
           {"name": "document", "share": 0.15,
            "prompt": {"source": "random_text", "min_tokens": 100,
                       "max_tokens": 150},
            "output": {"source": "uniform", "min_tokens": 4,
                       "max_tokens": 9}}]}


def sizes(reqs):
    return Counter((len(r.prompt.encode()), r.max_new_tokens, r.cls)
                   for r in reqs)


def test_same_seed_same_inputs_and_large_seeds():
    a = traffic.generate(MIX, 2 ** 31 + 99, 10)
    b = traffic.generate(MIX, 2 ** 31 + 99, 10)
    assert [(r.due_s, r.prompt, r.max_new_tokens) for r in a] == \
        [(r.due_s, r.prompt, r.max_new_tokens) for r in b]


def test_seeds_permute_a_fixed_window_of_work():
    w = traffic.window_count(MIX, 10)
    assert w == 20
    a = traffic.generate(MIX, 1, 10)
    b = traffic.generate(MIX, 2, 10)
    assert len(a) == len(b) == 2 * w + 20
    assert sizes(a[:w]) == sizes(b[:w])
    assert sizes(a[w:]) == sizes(b[w:])
    assert [r.prompt for r in a[:w]] != [r.prompt for r in b[:w]]
    gaps = lambda rs: sorted(round(y.due_s - x.due_s, 9)
                             for x, y in zip(rs, rs[1:w + 1]))
    assert gaps(a) == gaps(b)
    assert sum(r.cls == "document" for r in a[:w]) == 3
    assert all(y.due_s >= x.due_s for x, y in zip(a, a[1:]))


def test_backlog_is_due_at_the_start():
    mix = {"arrival": {"process": "backlog", "count": 16},
           "classes": [{"name": "q", "share": 1,
                        "prompt": {"source": "corpus"},
                        "output": {"source": "uniform", "min_tokens": 8,
                                   "max_tokens": 23}}]}
    reqs = traffic.generate(mix, 5, 10)
    assert len(reqs) == 16 and all(r.due_s == 0 for r in reqs)
    assert sorted(r.max_new_tokens for r in reqs) == list(range(8, 24))
    assert sizes(reqs) == sizes(traffic.generate(mix, 6, 10))
