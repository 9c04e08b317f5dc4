"""The served path's Pallas kernels compile for a TPU v5e chip.

Nothing runs: each test lowers a kernel at proxy-8b's widths (GQA group
G=4, head_dim 128, bf16) or at arctic-embed-m's width (D=768) for one chip
of a described ``v5e:2x2`` topology, compiles it with the TPU compiler,
and looks for the Pallas ``tpu_custom_call`` in the compiled program.
Interpret-mode tests cannot see what Mosaic refuses (unaligned block
shapes, too much fast memory); these can, at no chip time.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention.kernel import decode_attention_kernel
from repro.kernels.decode_attention.ops import flash_decode_paged
from repro.kernels.similarity_topk.kernel import similarity_topk_kernel

B, H, KV, HD = 8, 32, 8, 128          # proxy-8b: 32 heads over 8 KV heads


@pytest.fixture(scope="module")
def one_chip():
    # described here, never at import: only one process at a time may
    # load the TPU library, and every test worker imports this file
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep the cache out of these tests
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("smax", [32, 2048])
def test_decode_attention_kernel_compiles_for_v5e(one_chip, smax):
    text = _compile_text(
        lambda q, k, v, n: decode_attention_kernel(q, k, v, n,
                                                   interpret=False),
        one_chip, ((B, H, HD), jnp.bfloat16), ((B, KV, smax, HD), jnp.bfloat16),
        ((B, KV, smax, HD), jnp.bfloat16), ((B,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("smax", [32, 2048])
def test_flash_decode_paged_compiles_for_v5e(one_chip, smax):
    bs = 32
    nb = smax // bs
    text = _compile_text(
        lambda q, kp, vp, t, n: flash_decode_paged(q, kp, vp, t, n,
                                                   impl="pallas"),
        one_chip, ((B, 1, H, HD), jnp.bfloat16),
        ((B * nb + 1, bs, KV, HD), jnp.bfloat16),
        ((B * nb + 1, bs, KV, HD), jnp.bfloat16),
        ((B, nb), jnp.int32), ((B,), jnp.int32))
    assert "tpu_custom_call" in text


def test_similarity_topk_compiles_for_v5e(one_chip):
    text = _compile_text(
        lambda q, c: similarity_topk_kernel(q, c, 10, interpret=False),
        one_chip, ((64, 768), jnp.float32), ((100_000, 768), jnp.float32))
    assert "tpu_custom_call" in text
