"""The paper's 8-dataset prompt corpus, copied into the benchmark.

A copy of ``generate_corpus`` from the program's ``repro.data.benchmarks``
(Table 1 proportions, per-dataset output lengths), kept here so that no
change to the program can change the benchmark's traffic. One token per
byte under the program's byte tokenizer.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List

import numpy as np

# Table 1 baseline statistics (runs, success %) from the paper
BENCHMARK_STATS: Dict[str, dict] = {
    "humaneval":  {"runs": 820,    "base_success": 0.800, "kind": "code"},
    "gsm8k":      {"runs": 6595,   "base_success": 0.898, "kind": "math"},
    "mbpp":       {"runs": 2500,   "base_success": 0.694, "kind": "code"},
    "truthfulqa": {"runs": 3950,   "base_success": 0.802, "kind": "factual"},
    "arc":        {"runs": 5860,   "base_success": 0.803, "kind": "reasoning"},
    "hellaswag":  {"runs": 50210,  "base_success": 0.802, "kind": "commonsense"},
    "math":       {"runs": 25000,  "base_success": 0.796, "kind": "math"},
    "mmlu_pro":   {"runs": 60160,  "base_success": 0.700, "kind": "multitask"},
}
TOTAL_RUNS = 163720          # paper total
TOTAL_PROMPTS = 31019        # paper unique prompts
STRATEGIES = 5               # inference strategies per prompt

LOW_KEYWORDS = ["sum", "list", "define", "what is", "name", "count"]
HIGH_KEYWORDS = ["prove", "derive", "explain why", "step by step",
                 "justify", "analyze"]

# complexity mix per benchmark: P(low), P(medium), P(high)
COMPLEXITY_MIX = {
    "humaneval":  (0.25, 0.50, 0.25),
    "gsm8k":      (0.30, 0.50, 0.20),
    "mbpp":       (0.40, 0.45, 0.15),
    "truthfulqa": (0.35, 0.45, 0.20),
    "arc":        (0.30, 0.45, 0.25),
    "hellaswag":  (0.55, 0.35, 0.10),
    "math":       (0.10, 0.40, 0.50),
    "mmlu_pro":   (0.20, 0.45, 0.35),
}

# P(an indicative keyword appears | tier) — keyword routing is useful but
# imperfect, reproducing the paper's keyword/semantic gap
KEYWORD_EMIT = {"low": 0.80, "medium": 0.35, "high": 0.75}

# expected new-token output length (mean, std) per benchmark kind
OUTPUT_LEN = {
    "code": (180, 90), "math": (120, 60), "factual": (60, 30),
    "reasoning": (80, 40), "commonsense": (30, 15), "multitask": (70, 40),
}

TIERS = ("low", "medium", "high")

_SUBJECTS = ["the sequence", "a binary tree", "the dataset", "this function",
             "the equation", "a physical system", "the market model",
             "an enzyme pathway", "the training loop", "a state machine"]
# tier-correlated lexical cues: the semantic signal a learned classifier can
# exploit beyond the explicit router keywords (mimics what DistilBERT picks
# up from real prompts — phrasing, hedging, scaffolding)
_TIER_CUES = {
    "low": ["briefly", "directly", "in one line", "simply"],
    "medium": ["as usual", "in the standard way", "concisely but fully"],
    "high": ["rigorously", "with full justification", "considering corner "
             "cases and asymptotics", "via a multi-step argument"],
}
_CUE_EMIT = 0.9
_TASKS_LOW = ["write down", "output", "return", "compute", "give"]
_TASKS_HIGH = ["carefully work through", "rigorously show", "formally verify",
               "derive from first principles"]
_FILLERS = ["considering all edge cases", "for n up to 10^9",
            "under the stated constraints", "with full intermediate steps",
            "in the general case", "given the context above"]


@dataclass(frozen=True)
class Prompt:
    uid: int
    benchmark: str
    text: str
    complexity: str            # ground-truth tier: low | medium | high
    out_tokens: int            # tokens needed for a valid completion
    base_success: float        # Table-1 baseline completion probability


def _sample_tier(rng, bench: str) -> str:
    return TIERS[rng.choice(3, p=COMPLEXITY_MIX[bench])]


def _make_text(rng, bench: str, tier: str) -> str:
    subj = _SUBJECTS[rng.randint(len(_SUBJECTS))]
    filler = _FILLERS[rng.randint(len(_FILLERS))]
    parts = []
    if rng.rand() < KEYWORD_EMIT[tier]:
        pool = LOW_KEYWORDS if tier == "low" else (
            HIGH_KEYWORDS if tier == "high" else LOW_KEYWORDS + HIGH_KEYWORDS)
        parts.append(pool[rng.randint(len(pool))].capitalize())
    else:
        parts.append(_TASKS_LOW[rng.randint(len(_TASKS_LOW))].capitalize()
                     if tier != "high" else
                     _TASKS_HIGH[rng.randint(len(_TASKS_HIGH))].capitalize())
    parts.append(f"{subj} ({bench})")
    # high-tier prompts are longer (paper: complexity correlates with, but
    # is not determined by, length — we add noise)
    n_extra = {"low": 1, "medium": 2, "high": 4}[tier] + rng.randint(0, 3)
    parts.extend(rng.permutation(_FILLERS)[:n_extra].tolist())
    if rng.rand() < _CUE_EMIT:
        cues = _TIER_CUES[tier]
        parts.insert(1 + rng.randint(0, 2), cues[rng.randint(len(cues))])
    parts.append(filler)
    return " ".join(parts) + "."


def generate_corpus(n_prompts: int = 2000, seed: int = 0) -> List[Prompt]:
    """Corpus with the paper's benchmark proportions (scaled to n_prompts)."""
    rng = np.random.RandomState(seed)
    total = sum(s["runs"] for s in BENCHMARK_STATS.values())
    prompts: List[Prompt] = []
    uid = 0
    for bench, stats in BENCHMARK_STATS.items():
        n = max(8, round(n_prompts * stats["runs"] / total))
        mu, sd = OUTPUT_LEN[stats["kind"]]
        for _ in range(n):
            tier = _sample_tier(rng, bench)
            ot = int(np.clip(rng.normal(mu, sd), 8, 512))
            # harder prompts need longer outputs
            ot = int(ot * {"low": 0.7, "medium": 1.0, "high": 1.5}[tier])
            prompts.append(Prompt(
                uid=uid, benchmark=bench, text=_make_text(rng, bench, tier),
                complexity=tier, out_tokens=ot,
                base_success=stats["base_success"]))
            uid += 1
    rng.shuffle(prompts)
    return prompts
