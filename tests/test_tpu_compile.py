"""Compile-only guards: the codec kernels of the main path, compiled (not
interpreted) for a described v5e chip at smollm_135m's real ZeRO-1 bucket
sizes.  Nothing runs; the TPU compiler must accept each kernel and emit a
``tpu_custom_call``.  Interpret mode cannot catch tiling, lowering or VMEM
refusals, which is what these guard.

The topology is described inside a fixture (never at import): only the
worker that runs this file loads the TPU compiler, and it skips where the
topology cannot be described."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.core import codec
from repro.kernels import ops
from repro.models import transformer
from repro.optim import zero1

WIDTH = 5  # the default gradient/weight codec width
N_CHIPS = 4  # v5e:2x2


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile cannot be read back without the chip: keep
    # the persistent cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chunk():
    """One device's ZeRO-1 chunk of smollm_135m's parameter bucket on a
    4-chip data axis (every parameter is in the one bf16 bucket)."""
    meta = zero1.plan_buckets(
        transformer.abstract_params(configs.get("smollm_135m")), N_CHIPS)
    (padded,) = meta.padded
    return padded // N_CHIPS


def _compile_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_encode_fused_compiles_for_v5e(one_chip, chunk, dtype):
    """The reduce-scatter's send side: every chunk of the bucket encoded
    in one fused pass (``encode_fused_chunks`` on (n_dev, chunk))."""
    x = jax.ShapeDtypeStruct((N_CHIPS, chunk), jnp.dtype(dtype),
                             sharding=one_chip)
    text = _compile_text(
        lambda v: ops.encode_fused_chunks(v, WIDTH, use_pallas=True,
                                          interpret=False), x)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_decode_reduce_compiles_for_v5e(one_chip, chunk, dtype):
    """The reduce-scatter's receive side: one received chunk decoded into
    the f32 accumulator (``chunk // 32`` groups: not a whole number of
    kernel tiles, so the last tile is partial)."""
    lo_bits = codec.LAYOUTS[dtype].lo_bits
    n_g = chunk // 32
    u32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.uint32,
                                              sharding=one_chip)
    acc = jax.ShapeDtypeStruct((chunk,), jnp.float32, sharding=one_chip)
    text = _compile_text(
        lambda p, lo, gb, a: ops.decode_reduce(
            p, lo, gb, a, dtype, WIDTH, use_pallas=True, interpret=False),
        u32(n_g * WIDTH), u32(n_g * lo_bits), u32(n_g), acc)
    assert "tpu_custom_call" in text


def test_exception_indices_compiles_lean_for_v5e(one_chip):
    """The weight-sync delta's exception index at smollm_135m's whole
    134,515,200-element bucket and its 2% list: its temporaries stay under
    two bytes per mask element.  Packing the mask as a lane-padded
    ``(n / 32, 32)`` uint32 array would take 2.15 GB; ``jnp.nonzero``
    takes 1.08 GB."""
    from repro.core import packing

    n = 134_515_200
    size = int(np.ceil(n * 0.02))
    mask = jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=one_chip)
    compiled = jax.jit(
        lambda m: packing.exception_indices(m, size=size, fill=n)
    ).lower(mask).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * n


@pytest.mark.parametrize("side", ["pack", "unpack"])
def test_bitplane_codec_compiles_lean_for_v5e(one_chip, side):
    """The bit-plane packer and unpacker at glm4_9b's whole
    359,154,176-element weight-sync bucket: temporaries under four bytes
    per element.  One lane-padded ``(n / 32, 32)`` uint32 array, which the
    lane-narrow form materialised, is sixteen."""
    from repro.core import packing

    n = 359_154_176
    if side == "pack":
        arg = jax.ShapeDtypeStruct((n,), jnp.uint32, sharding=one_chip)
        fn = lambda v: packing.bitplane_pack(v, WIDTH)  # noqa: E731
    else:
        arg = jax.ShapeDtypeStruct((n // 32 * WIDTH,), jnp.uint32,
                                   sharding=one_chip)
        fn = lambda p: packing.bitplane_unpack(p, WIDTH)  # noqa: E731
    compiled = jax.jit(fn).lower(arg).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * n
