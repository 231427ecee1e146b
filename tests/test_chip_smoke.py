"""``chip_smoke.py`` rehearsed on the CPU: its phase functions at a tiny
size with the Pallas kernels interpreted, and the script's refusal to
run without a TPU or outside the repository."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs.registry import ARCHS
from repro.kernels import ops
from repro.launch.serve import CHECKOUT, use_compile_cache

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"
MAX_SEQ = 256


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cfg():
    return ARCHS["smollm-360m"].reduced()       # bf16, as on the chip


def test_compile_cache_placement(monkeypatch, tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` wins untouched; otherwise the cache
    goes to the checkout's fixed, git-ignored ``.jax_cache``."""
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert use_compile_cache() == str(CHECKOUT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(
            CHECKOUT / ".jax_cache")
        ignored = (CHECKOUT / ".gitignore").read_text().split()
        assert ".jax_cache/" in ignored
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_device_phase_refuses_the_cpu(smoke):
    with pytest.raises(smoke.SmokeFailure, match="no TPU"):
        smoke.phase_device()


def test_kernel_phase(smoke, cfg):
    smoke.phase_kernels(cfg, MAX_SEQ, batch=2, interpret=True)


def test_serve_phase(smoke, cfg):
    with ops.kernel_dispatch("interpret"):      # read at trace time
        smoke.phase_serve("smollm-360m", cfg, MAX_SEQ)


def test_logits_phase(smoke, cfg):
    assert smoke.phase_logits(cfg, MAX_SEQ, "interpret") <= smoke.LOGIT_TOL


@pytest.mark.parametrize("alone", [False, True])
def test_script_fails_without_tpu_or_repo(alone, tmp_path):
    """No TPU (``JAX_PLATFORMS=cpu``), or the script copied out of the
    repository: a non-zero exit and no result line."""
    script = SCRIPT
    if alone:
        script = tmp_path / SCRIPT.name
        shutil.copy(SCRIPT, script)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, str(script)], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    lines = proc.stdout.strip().splitlines()
    assert not lines or '"ok"' not in lines[-1]
    if not alone:
        assert "no TPU" in proc.stderr
        with pytest.raises(json.JSONDecodeError):
            json.loads(lines[-1] if lines else "")
