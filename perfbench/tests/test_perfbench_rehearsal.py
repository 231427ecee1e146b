"""A cell rehearsed in-process on the CPU, the paged kernels in interpret
mode: the result has the shape of the benchmark's result line."""
import json

from perfbench.tests.rehearsal import rehearse


def test_rehearsal_prints_the_result_line(monkeypatch):
    out = rehearse(monkeypatch, mode="interpret")
    line = json.loads(json.dumps(out))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "compared"
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"itl_p50_s", "itl_p95_s", "setup_s"}
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(line["device"])
    for c in line["compared"].values():
        assert set(c) == {"value", "limit"}
