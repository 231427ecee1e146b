"""Phase spans of the serve step and named scopes of the model step.

The serve step marks its phases with ``repro.obs.phase`` (profiler
spans on the device trace's clock), the model step names its parts with
``jax.named_scope``. Checked here on the CPU: the spans nest as the
serve loop runs them, the lowered programs carry the scopes, and TTFT is
stamped when the first token reaches the host.
"""
import dataclasses
import glob
import os
import re
import time

import jax
import jax.numpy as jnp
import pytest

from conftest import reduced_f32
from repro.core.gateway import ServeFrontend
from repro.core.orchestrator import SpinConfig
from repro.models.attention import paged_gather_ctx
from repro.models.transformer import init_lm, init_paged_cache
from repro.serving.backend import BACKENDS
from repro.serving.engine import compile_paged_fns, init_device_state

SMOL = "smollm-360m"
PROGRAM = ("gateway.", "scheduler.", "engine.", "kv.", "pool.")
# span -> the span it must sit in (None: top level of the serving thread)
PARENT = {
    "gateway.submit": None,
    "gateway.step": None,
    "gateway.deliver": "gateway.step",
    "scheduler.step": "gateway.step",
    "scheduler.dispatch": "scheduler.step",
    "scheduler.gauges": "scheduler.step",
    "engine.step": "scheduler.step",
    "engine.admit": "engine.step",
    "engine.prefill": "engine.step",
    "engine.first_tokens": "engine.step",
    "engine.decode": "engine.step",
    "engine.record": "engine.step",
}
SYNC_PARENTS = ("engine.first_tokens", "engine.decode")
SCOPES = ("embed", "attn", "mlp", "unembed", "weight_cast")


@pytest.fixture(scope="module")
def fe():
    spin = SpinConfig(window_s=20.0, cooldown_s=0.0, idle_tau_s=0.5,
                      tick_s=3600.0, max_replicas=1,
                      warm_pool={"small": 0, "medium": 0, "large": 0})
    return ServeFrontend({SMOL: reduced_f32(SMOL)}, max_seq=96, spin=spin,
                         paged=True)


def _host_spans(trace_dir):
    """(name, start_ns, end_ns, thread) of the program's spans."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PROGRAM):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns, line.name))
    return out


def _parent(span, spans):
    """Innermost program span on the same thread that holds ``span``."""
    name, s, e, th = span
    holders = [x for x in spans if x is not span and x[3] == th
               and x[1] <= s and e <= x[2]]
    return min(holders, key=lambda x: x[2] - x[1])[0] if holders else None


def test_phase_spans_nest_as_the_serve_loop_runs_them(fe, tmp_path):
    fe.submit("warm the replica up", max_new_tokens=2)
    fe.serve_all()                       # spin-up and compiles, untraced
    with jax.profiler.trace(str(tmp_path)):
        hs = [fe.submit(p, max_new_tokens=5)
              for p in ("count the numbers please", "add these up",
                        "a longer prompt that needs more than one chunk")]
        fe.serve_all()
    assert all(h.response.completed for h in hs)
    spans = _host_spans(str(tmp_path))
    names = {s[0] for s in spans}
    assert set(PARENT) | {"engine.sync"} <= names, sorted(names)
    for span in spans:
        parent = _parent(span, spans)
        if span[0] == "engine.sync":
            assert parent in SYNC_PARENTS
        elif span[0] in PARENT:
            assert parent == PARENT[span[0]], (span[0], parent)


def test_ttft_is_the_first_token_reaching_the_host(fe, monkeypatch):
    fe.submit("warm", max_new_tokens=2)
    fe.serve_all()
    h = fe.submit("how long until the first token", max_new_tokens=3)
    fe.serve_all()
    span, = [s for s in fe.obs.tracer.finished if s.uid == h.uid]
    assert h.response.ttft_s == span.first_token_t - span.submit_t
    assert 0 < h.response.ttft_s <= h.response.latency_s

    # every host wait on the device now takes 50 ms: the first token's
    # wait is inside TTFT
    get = jax.device_get

    def slow_get(x):
        time.sleep(0.05)
        return get(x)
    monkeypatch.setattr(jax, "device_get", slow_get)
    h = fe.submit("and with a slow device", max_new_tokens=3)
    fe.serve_all()
    span, = [s for s in fe.obs.tracer.finished if s.uid == h.uid]
    assert h.response.ttft_s >= 0.05
    assert h.response.ttft_s == span.first_token_t - span.submit_t


def _scopes(lowered) -> set:
    """Every name-stack component in a lowered program's locations."""
    locs = re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True))
    return {part for loc in locs for part in loc.split("/")}


@pytest.fixture(scope="module")
def shapes():
    """bf16 compute over float32 weights, as the served configurations
    run, so the weight casts are real converts."""
    cfg = dataclasses.replace(reduced_f32(SMOL), dtype="bfloat16")
    be = BACKENDS["vllm"]
    fns = compile_paged_fns(cfg, be, 64, 16)
    params = jax.eval_shape(lambda: init_lm(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: init_paged_cache(cfg, 16, 16))
    state = jax.eval_shape(lambda: init_device_state(be.max_batch, 4))
    return fns, params, cache, state


def test_fused_decode_step_names_its_scopes(shapes):
    fns, params, cache, state = shapes
    got = _scopes(fns.fused_step.lower(params, cache, state))
    assert set(SCOPES) | {"sample", "kv_write"} <= got


def test_chunk_prefill_names_its_scopes(shapes):
    fns, params, cache, _ = shapes
    i32 = lambda s=(): jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    ctx = jax.eval_shape(paged_gather_ctx, cache, i32((1,)))
    got = _scopes(fns.prefill.lower(params, i32((1, 8)), ctx, i32(), i32()))
    assert set(SCOPES) <= got
    assert "kv_gather" in _scopes(fns.gather.lower(cache, i32((1,))))
    assert "kv_scatter" in _scopes(fns.scatter.lower(
        cache, ctx, i32((4,)), i32(), i32()))
