"""The Pallas kernels compile for a TPU v5e, at the widths of the models
the repo serves.

Nothing runs: the TPU compiler that ships with JAX compiles each kernel
for a described ``v5e:2x2`` chip, which refuses what interpret mode
accepts (unaligned blocks, primitives Mosaic cannot lower, broadcasts
it does not implement).  Each case asserts the compiled program holds
the kernel (``tpu_custom_call``), so a kernel can never silently give
way to plain XLA on the chip.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and test workers import every
test file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler to describe a chip with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shapes(sharding, *specs):
    return [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in specs]


F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32

# (kernel wrapper, static kwargs, argument shapes)
CASES = {
    # en-zh Marian encoder / teacher-forced attention: 8 heads x 64
    "flash_attention-marian": (
        ops.flash_attention, dict(causal=False),
        [((4, 64, 8, 64), F32)] * 3 + [((4,), I32)]),
    # qwen3-8b prefill attention: GQA 32 query / 8 kv heads x 128, bf16
    "flash_attention-gqa-bf16": (
        ops.flash_attention, dict(causal=True),
        [((1, 256, 32, 128), BF16)] + [((1, 256, 8, 128), BF16)] * 2
        + [((1,), I32)]),
    # Marian decode against a 256-slot KV cache
    "flash_decode-marian": (
        ops.flash_decode, {},
        [((4, 8, 64), F32)] + [((4, 256, 8, 64), F32)] * 2 + [((4,), I32)]),
    # rwkv6-3b: 40 heads x 64, chunk 32, with a carried state
    "rwkv6_wkv-rwkv6-3b": (
        ops.rwkv6_wkv, dict(chunk=32),
        [((1, 64, 40, 64), F32)] * 4 + [((40, 64), F32),
                                        ((1, 40, 64, 64), F32)]),
    # zamba2-1.2b mamba2: 64 heads x 64, state 64, chunk 128; 200 tokens
    # exercise the padded tail chunk
    "ssd_scan-zamba2-1.2b": (
        ops.ssd_scan, dict(chunk=128),
        [((1, 200, 64, 64), F32), ((1, 200, 64), F32), ((64,), F32),
         ((1, 200, 64, 64), F32), ((1, 200, 64, 64), F32)]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip):
    fn, kw, specs = CASES[case]
    lowered = fn.lower(*_shapes(one_chip, *specs), interpret=False, **kw)
    assert "tpu_custom_call" in lowered.compile().as_text()
