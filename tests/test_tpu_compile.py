"""The main path's kernels compile for the chip — without the chip.

The TPU's compiler is installed beside the CPU backend and compiles for a
chip that is described, not attached (``jax.experimental.topologies``). These
AOT compiles raise what Mosaic would raise on a v5e at the widths the system
runs: tiling, VMEM and layout refusals that interpret mode cannot show. A
compile that passes is not a chip run: nothing executes here.

Skipped where the topology cannot be described (no libtpu).
"""

from __future__ import annotations

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from hypha_tpu.ops.flash_attention import flash_attention
from hypha_tpu.ops.paged_attention import PagedKV, paged_attention


@pytest.fixture(scope="module")
def chip():
    """One described v5e chip's sharding; persistent compile cache off (an
    entry written for an unattached chip cannot be read back and warns)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


# GPT-2-small's step (B16 S1024 H12 D64) and Mistral-7B's long-context GQA
# (B2 S4096 H32/Hkv8 D128).
FLASH_SHAPES = {"gpt2": (16, 1024, 12, 12, 64), "mistral": (2, 4096, 32, 8, 128)}


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("shape", list(FLASH_SHAPES))
def test_flash_attention_compiles_for_v5e(chip, shape, direction):
    B, S, H, Hkv, D = FLASH_SHAPES[shape]
    q = jax.ShapeDtypeStruct((B, S, H, D), jnp.bfloat16, sharding=chip)
    kv = jax.ShapeDtypeStruct((B, S, Hkv, D), jnp.bfloat16, sharding=chip)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = fwd if direction == "fwd" else jax.grad(loss, argnums=(0, 1, 2))
    assert "tpu_custom_call" in _compiled_text(fn, q, kv, kv)


@pytest.mark.parametrize("quant", ["bf16", "int8"])
def test_ragged_paged_attention_compiles_for_v5e(chip, quant):
    """The serving decode kernel at head_dim 128 (the Mosaic lane width),
    Sq=1, block_size 16 — bf16 blocks and int8 blocks with scales."""
    B, Hq, Hkv, D, bs, blocks, max_blocks = 8, 32, 8, 128, 16, 512, 64
    rows = (blocks + 1) * bs

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    payload = jnp.int8 if quant == "int8" else jnp.bfloat16
    scale = sds((rows, Hkv), jnp.float32) if quant == "int8" else None
    kv = PagedKV(
        sds((rows, Hkv, D), payload), sds((rows, Hkv, D), payload),
        scale, scale, sds((B, max_blocks), jnp.int32),
    )

    def decode(q, kv, q_offset):
        return paged_attention(
            q, kv, blocks=blocks, block_size=bs, q_offset=q_offset,
            use_kernel=True, interpret=False,
        )

    text = _compiled_text(
        decode, sds((B, 1, Hq, D), jnp.bfloat16), kv, sds((B,), jnp.int32)
    )
    assert "tpu_custom_call" in text
