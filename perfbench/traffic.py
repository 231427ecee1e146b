"""One general traffic generator, driven by a mix file under ``traffic/``.

A mix file is JSON::

    {"arrival": {"process": "poisson", "rate_per_s": 1.4}
              | {"process": "backlog", "count": 48},
     "classes": [{"name": ..., "share": ...,
                  "prompt": {"source": "corpus"}
                          | {"source": "random_text", "min_tokens": a, "max_tokens": b},
                  "output": {"source": "corpus"}
                          | {"source": "uniform", "min_tokens": a, "max_tokens": b}}]}

Every seed gets the same multiset of request sizes and inter-arrival
gaps, in another order: the sizes come from a fixed corpus draw and from
evenly spaced quantiles, the gaps from evenly spaced quantiles of the
exponential distribution, and ``--seed`` only shuffles each (a plain
permutation, so arrivals stay a Poisson process and bursts of short
gaps or of long requests happen as they would) and writes the text of
the unshared prompts. So the seed changes the order and the weights,
not the amount of work. One token is one byte (the program's
byte tokenizer); all text is ASCII.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from typing import List, Tuple

import numpy as np

from perfbench.corpus import generate_corpus

CORPUS_SIZE = 8192          # fixed corpus draw; requests take its head
CORPUS_SEED = 0


@dataclass
class TrafficRequest:
    """One request of a run, in the order of its due times."""
    index: int
    due_s: float                 # offset from the window start
    prompt: str
    max_new_tokens: int
    cls: str                     # class name from the mix file
    dataset: str = ""            # corpus dataset, when drawn from the corpus


def load_mix(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _quantiles(lo: int, hi: int, n: int) -> List[int]:
    """n evenly spaced integer points of [lo, hi] (midpoint quantiles)."""
    return [int(round(lo + (hi - lo) * (i + 0.5) / n)) for i in range(n)]


def _random_text(rng: np.random.Generator, n: int) -> str:
    """n bytes of lower-case letters: a prompt that shares no prefix."""
    return rng.integers(97, 123, size=n, dtype=np.uint8).tobytes().decode()


def _class_counts(classes: List[dict], n: int) -> List[int]:
    """Largest-remainder split of n requests by the classes' shares."""
    total = sum(c["share"] for c in classes)
    raw = [n * c["share"] / total for c in classes]
    counts = [int(math.floor(r)) for r in raw]
    order = sorted(range(len(classes)), key=lambda i: raw[i] - counts[i],
                   reverse=True)
    for i in order[:n - sum(counts)]:
        counts[i] += 1
    return counts


def window_count(mix: dict, seconds: float) -> int:
    """Requests due inside the window: a backlog's whole count, or an
    open loop's rate times the window."""
    arr = mix["arrival"]
    if arr["process"] == "backlog":
        return int(arr["count"])
    if arr["process"] == "poisson":
        return max(1, int(round(arr["rate_per_s"] * seconds)))
    raise ValueError(f"unknown arrival process {arr['process']!r}")


def _block(mix: dict, n: int, corpus, taken: int, rng, fixed
           ) -> Tuple[List[dict], int]:
    """n requests split over the mix's classes, in an order drawn from
    ``rng``; returns them and the number of corpus prompts consumed."""
    reqs: List[dict] = []
    for cls, count in zip(mix["classes"], _class_counts(mix["classes"], n)):
        p, o = cls["prompt"], cls["output"]
        if o["source"] == "uniform":
            outs = _quantiles(o["min_tokens"], o["max_tokens"], count)
            outs = [outs[i] for i in fixed.permutation(count)]
        elif o["source"] != "corpus":
            raise ValueError(f"unknown output source {o['source']!r}")
        if p["source"] == "random_text":
            lens = _quantiles(p["min_tokens"], p["max_tokens"], count)
        elif p["source"] != "corpus":
            raise ValueError(f"unknown prompt source {p['source']!r}")
        for j in range(count):
            r = {"cls": cls["name"], "dataset": ""}
            if p["source"] == "corpus":
                if taken >= len(corpus):
                    raise ValueError("traffic needs more corpus prompts "
                                     f"than the {CORPUS_SIZE} drawn")
                q = corpus[taken]
                taken += 1
                r["prompt"], r["dataset"] = q.text, q.benchmark
                r["max_new_tokens"] = q.out_tokens
            else:
                r["prompt"] = lens[j]          # text written below, by seed
            if o["source"] == "uniform":
                r["max_new_tokens"] = outs[j]
            reqs.append(r)
    reqs = [reqs[i] for i in rng.permutation(len(reqs))]
    for r in reqs:
        if isinstance(r["prompt"], int):
            r["prompt"] = _random_text(rng, r["prompt"])
    return reqs, taken


def _gaps(n: int, rate: float, rng) -> np.ndarray:
    """n exponential inter-arrival gaps at evenly spaced quantiles, in an
    order drawn from ``rng``."""
    g = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    return g[rng.permutation(n)]


def generate(mix: dict, seed: int, seconds: float) -> List[TrafficRequest]:
    """The run's requests, sorted by due time.

    An open loop makes two blocks: the requests due inside the window
    (rate x window of them), then as many again plus ten seconds' worth
    that keep arriving while the window's last requests finish. Each
    block's sizes and gaps are the same for every seed; the seed orders
    them. A backlog is one block, all due at the window start."""
    rng = np.random.default_rng(seed)
    fixed = np.random.default_rng(CORPUS_SEED)
    corpus = generate_corpus(CORPUS_SIZE, seed=CORPUS_SEED)
    arr = mix["arrival"]
    k = window_count(mix, seconds)
    reqs, taken = _block(mix, k, corpus, 0, rng, fixed)
    if arr["process"] == "poisson":
        rate = arr["rate_per_s"]
        tail, _ = _block(mix, k + int(math.ceil(rate * 10)), corpus, taken,
                         rng, fixed)
        gaps = np.concatenate([_gaps(k, rate, rng),
                               _gaps(len(tail), rate, rng)])
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        reqs += tail
    else:
        due = np.zeros(len(reqs))
    return [TrafficRequest(index=i, due_s=float(due[i]), prompt=r["prompt"],
                           max_new_tokens=int(r["max_new_tokens"]),
                           cls=r["cls"], dataset=r["dataset"])
            for i, r in enumerate(reqs)]


def max_prompt_tokens(reqs: List[TrafficRequest]) -> int:
    return max(len(r.prompt.encode()) for r in reqs)

