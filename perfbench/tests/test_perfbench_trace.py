"""Trace reduction: busy and idle time, op times and gap attribution,
checked by hand on made-up events and against the raw events of a small
trace recorded on a TPU v5e."""
import glob
import lzma
import os

import pytest

from perfbench import tracereduce

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures")


def test_reduction_by_hand():
    host = [("harness.window", 10.0, 20.0),
            ("harness.step", 10.0, 14.0),
            ("harness.wait", 14.0, 16.0),
            ("harness.submit", 16.0, 16.5),
            ("harness.step", 16.5, 20.0)]
    ops = {"/device:TPU:0": [
        ("decode", 9.0, 11.0),        # clipped to 10..11
        ("matmul", 10.5, 12.0),       # overlaps: union 10..12
        ("decode", 13.0, 14.0),
        ("prefill", 17.0, 19.5),
        ("decode", 21.0, 22.0)]}      # outside the window
    s = tracereduce.reduce_events(ops, host)
    assert s.window_s == 10.0
    assert s.busy_s == pytest.approx(2.0 + 1.0 + 2.5)
    assert s.op_s == pytest.approx({"decode": 2.0, "matmul": 1.5,
                                    "prefill": 2.5})
    assert s.seconds_matching("dec|pre") == pytest.approx(4.5)
    # gaps 12..13 (step), 14..17 (mid 15.5: wait), 19.5..20 (step)
    assert [g[0] for g in s.top_gaps] == ["harness.wait", "harness.step",
                                          "harness.step"]
    assert [g[1] for g in s.top_gaps] == pytest.approx([3.0, 1.0, 0.5])
    assert s.top_ops[0] == ["prefill", pytest.approx(2.5)]


def _recorded(tmp_path):
    """The committed trace (1.5 s of smollm360m-zeroshot on a TPU v5e,
    xz-compressed), unpacked."""
    packed = glob.glob(os.path.join(FIXTURE, "*.xplane.pb.xz"))
    if not packed:
        pytest.fail("no recorded trace under perfbench/tests/fixtures")
    path = tmp_path / "trace.xplane.pb"
    path.write_bytes(lzma.decompress(open(packed[0], "rb").read()))
    return str(path)


def test_recorded_trace_against_its_events(tmp_path):
    device, host = tracereduce.read_xplane(_recorded(tmp_path))
    s = tracereduce.reduce_events(device, host)
    (lo, hi), = [(a, b) for n, a, b in host if n == "harness.window"]
    assert s.window_s == pytest.approx(hi - lo)
    assert len(device) == 1
    ops = next(iter(device.values()))
    clipped = sorted((max(a, lo), min(b, hi)) for _, a, b in ops
                     if min(b, hi) > max(a, lo))
    # busy: walk the sorted intervals by hand
    busy, end = 0.0, lo
    for a, b in clipped:
        if b > end:
            busy += b - max(a, end)
            end = b
    assert s.busy_s == pytest.approx(busy, rel=1e-9)
    assert 0 < s.busy_s <= s.window_s
    # loops enclose the ops they run: op time counts the ops alone
    assert sum(s.op_s.values()) == pytest.approx(
        sum(min(b, hi) - max(a, lo) for n, a, b in ops
            if min(b, hi) > max(a, lo)
            and not n.startswith(("while", "conditional", "call"))),
        rel=1e-9)
    # idle gaps sum to the window less the busy time
    gaps = tracereduce.idle_gaps(clipped, lo, hi)
    assert sum(b - a for a, b in gaps) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-6)
    assert s.top_gaps and all(g[0].startswith("harness.") for g in s.top_gaps)
    # the decode kernel by name, and what the run printed from this trace
    assert s.seconds_matching(r"^paged_decode_attention") == pytest.approx(
        0.47661059799999983, rel=1e-9)
    assert s.busy_s == pytest.approx(1.4769419880000048, rel=1e-9)
    assert s.window_s == pytest.approx(1.531756069, rel=1e-9)
