"""Compile the paged attention kernels for a described TPU v5e chip.

Interpret mode never checks Mosaic's tiling rules (a block's last two
dims divisible by (8, 128) or equal to the array's); the TPU compiler,
installed here, does, for a chip that is described and not attached.
Each case compiles one kernel in bf16 at a published head geometry and
checks that the Mosaic kernel is in the program. Nothing runs.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import ARCHS
from repro.kernels import ops

ARCH_NAMES = ["smollm-360m", "phi3-medium-14b", "glm4-9b"]
BS = 16                        # the engine's block size
MAX_SEQ = 2048                 # table length: 128 blocks per sequence
BATCH = 16                     # the largest backend batch (vllm profile)


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compile cache
    off (a cache entry written for an absent chip cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 -- any failure means no TPU compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(fn, shapes, sharding) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return fn.lower(*args, interpret=False).compile().as_text()


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_paged_decode_compiles_for_v5e(arch, one_chip):
    cfg = ARCHS[arch]
    hq, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    nbseq = MAX_SEQ // BS
    nb = BATCH * nbseq
    bf, i32 = jnp.bfloat16, jnp.int32
    text = _compiled_text(ops.paged_decode_attention, [
        ((BATCH, hq, d), bf), ((nb, hkv, BS, d), bf), ((nb, hkv, BS, d), bf),
        ((BATCH, nbseq), i32), ((BATCH,), i32)], one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("sb,ctx_blocks,block", [
    (8, 1, MAX_SEQ),           # the engine's call: context as one block
    (64, 1, MAX_SEQ),
    (64, 8, BS),               # context streamed through a block table
])
@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_paged_prefill_compiles_for_v5e(arch, sb, ctx_blocks, block,
                                        one_chip):
    cfg = ARCHS[arch]
    hq, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    nb = max(ctx_blocks, 4)
    bf, i32 = jnp.bfloat16, jnp.int32
    text = _compiled_text(ops.paged_prefill_attention, [
        ((sb, hq, d), bf), ((nb, hkv, block, d), bf),
        ((nb, hkv, block, d), bf), ((hkv, sb, d), bf), ((hkv, sb, d), bf),
        ((ctx_blocks,), i32), ((), i32), ((), i32)], one_chip)
    assert "tpu_custom_call" in text
