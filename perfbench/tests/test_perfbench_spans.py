"""Program spans and named scopes in a trace: idle time by innermost span,
device time by scope, and the per-step readings, checked by hand on
made-up events and against recorded TPU v5e traces."""
import lzma
import os

import pytest

from perfbench import spans, tracereduce

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures")


def _by_hand():
    host = [("harness.window", 10.0, 20.0),
            ("harness.step", 10.0, 14.0),
            ("gateway.step", 10.0, 14.0),
            ("scheduler.step", 10.5, 13.5),
            ("engine.step", 11.0, 13.0),
            ("engine.decode", 11.5, 12.8),
            ("engine.sync", 12.0, 12.8),
            ("harness.wait", 14.0, 16.0),
            ("harness.step", 16.5, 20.0),
            ("gateway.step", 16.5, 20.0)]
    ops = {"/device:TPU:0": [
        # clipped to 10..11; two nested scopes
        ("fusion.1", 9.0, 11.0,
         "jit(_fused)/while/body/closed_call/attn/weight_cast/convert"),
        ("fusion.2", 11.0, 12.0, "jit(_fused)/unembed/dot_general"),
        # no scope: counts under its program
        ("convert.3", 13.0, 14.0, "jit(convert_element_type)"),
        ("while.4", 16.5, 20.0, "jit(_fused)/while"),   # control flow
        ("fusion.5", 17.0, 19.5, "jit(_fused)/while/body/mlp/weight_cast/x"),
        ("fusion.6", 21.0, 22.0, "jit(_fused)/unembed/dot_general")]}
    return spans.Trace(ops=ops, host=host, modules={})


def test_idle_by_span_splits_gaps_across_spans():
    s = spans.reduce_trace(_by_hand())
    assert s.window_s == 10.0
    # busy: 10..12, 13..14, 16.5..20 (the loop encloses fusion.5)
    assert s.busy_s == pytest.approx(2.0 + 1.0 + 3.5)
    # gaps 12..13 (sync 0.8, then engine.step 0.2) and 14..16.5
    # (wait 2.0, uncovered 0.5)
    assert s.idle_by_span == pytest.approx({
        "engine.sync": 0.8, "engine.step": 0.2, "harness.wait": 2.0,
        "harness.loop": 0.5})
    assert sum(s.idle_by_span.values()) == pytest.approx(
        s.window_s - s.busy_s)
    assert s.idle_in["gateway.step"] == pytest.approx(1.0)
    assert s.idle_in["engine.step"] == pytest.approx(1.0)
    assert s.idle_in["engine.sync"] == pytest.approx(0.8)
    # each gap is named by the innermost span at its middle
    assert s.top_gaps == [["harness.wait", pytest.approx(2.5)],
                          ["engine.sync", pytest.approx(1.0)]]
    assert s.engine_steps == 1


def test_device_by_scope_counts_nested_scopes_and_unscoped_programs():
    s = spans.reduce_trace(_by_hand())
    assert s.device_by_scope == pytest.approx({
        "attn": 1.0, "weight_cast": 1.0 + 2.5, "mlp": 2.5, "unembed": 1.0,
        "jit_convert_element_type": 1.0})
    # ops alone: the loop and the op outside the window do not count
    assert s.op_s == pytest.approx(1.0 + 1.0 + 1.0 + 2.5)


def test_readers_by_hand():
    s = spans.reduce_trace(_by_hand())
    assert spans.host_idle_ms(s, 2) == pytest.approx(1e3 * 1.0 / 2)
    assert spans.scope_ms(s, "weight_cast", 2) == pytest.approx(1750.0)
    assert spans.scope_ms(s, "unembed", 2) == pytest.approx(500.0)
    assert spans.scope_ms(s, "embed", 2) == 0.0          # no op of it
    assert spans.host_idle_ms(None, 2) is None            # no trace
    assert spans.scope_ms(None, "unembed", 2) is None
    assert spans.host_idle_ms(s, 0) is None               # no steps
    assert spans.scope_ms(s, "unembed", 0) is None


@pytest.mark.parametrize("tf_op,module,stack", [
    ("jit(_fused)/while/body/closed_call/dot_general:", "jit__fused",
     "jit(_fused)/while/body/closed_call/dot_general"),
    (None, "jit__fused", "jit(_fused)"),
    ("params['embed']:", "jit__prefill", "jit(_prefill)"),
    (None, "jit_convert_element_type", "jit(convert_element_type)"),
])
def test_name_stack_falls_back_to_the_program(tf_op, module, stack):
    assert spans.name_stack(tf_op, module) == stack
    assert spans.program_of(stack) == module


def _unpacked(tmp_path, packed):
    path = tmp_path / "trace.xplane.pb"
    path.write_bytes(lzma.decompress(open(packed, "rb").read()))
    return str(path)


def test_recorded_trace_name_stacks(tmp_path):
    """The committed 1.5 s of smollm360m-zeroshot (recorded before the
    program named its spans and scopes): every op has a name stack, the
    events are those ``tracereduce`` reads, and the reductions agree."""
    path = _unpacked(tmp_path, os.path.join(
        FIXTURE, "smollm360m-zeroshot.xplane.pb.xz"))
    tr = spans.read(path)
    device, host = tracereduce.read_xplane(path)
    (chip, ops), = tr.ops.items()
    assert [o[:3] for o in ops] == device[chip]
    assert sorted(tr.host) == sorted(host)
    assert all(o[3].startswith("jit(") for o in ops)
    s = spans.reduce_trace(tr)
    old = tracereduce.reduce_events(device, host)
    assert (s.window_s, s.busy_s) == (old.window_s, old.busy_s)
    assert s.op_s == pytest.approx(sum(old.op_s.values()), rel=1e-9)
    assert s.device_by_scope["jit__fused"] > 0
    assert sum(s.device_by_scope.values()) == pytest.approx(s.op_s,
                                                            rel=1e-9)
    assert sum(s.idle_by_span.values()) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-9)
    # no program span in this trace: gaps keep the harness's names
    assert [g[0] for g in s.top_gaps] == [g[0] for g in old.top_gaps]
    assert s.launches["jit_convert_element_type"][0] == 31


def test_recorded_trace_with_program_spans(tmp_path):
    """1.5 s of glm4-9b-decode on a TPU v5e (its first steps: the backlog
    prefilling beside the first decodes), traced with the program's
    spans and scopes; the numbers are what the chip run printed."""
    path = _unpacked(tmp_path, os.path.join(
        FIXTURE, "spans", "glm4-9b-decode.xplane.pb.xz"))
    tr = spans.read(path)
    s = spans.reduce_trace(tr)
    (_, ops), = tr.ops.items()
    assert all(o[3].startswith("jit(") for o in ops)
    # every long gap falls inside the serve step, under a program span
    assert s.top_gaps and all(g[0].startswith(spans.PROGRAM_SPANS)
                              for g in s.top_gaps)
    assert s.top_gaps[1][0] == "engine.sync"
    assert sum(s.idle_by_span.values()) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-9)
    assert s.device_by_scope["weight_cast"] > 0
    assert s.device_by_scope["unembed"] > 0
    assert s.window_s == pytest.approx(1.508224303, rel=1e-9)
    assert s.busy_s == pytest.approx(1.3530478390000276, rel=1e-9)
    assert s.engine_steps == 32
    assert spans.host_idle_ms(s, s.engine_steps) == pytest.approx(
        4.664693874999135, rel=1e-9)
    assert spans.scope_ms(s, "unembed", s.engine_steps) == pytest.approx(
        7.901038468750007, rel=1e-9)
