"""AOT-compile the quantize kernels for a TPU v5e chip that is described, not
attached: every quantized halo exchange on the chip runs them with
``interpret=False``. Covers the widths of the GCN training path (hidden 256,
input features 602) at a boundary-buffer row count.

The topology is described inside a fixture (never at import), so every test
worker collects the same tests and only the one that runs this file loads the
TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.quantization import packed_width
from repro.kernels.quant.quant import quantize_pack, unpack_dequantize

ROWS = 20_000


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the persistent
    # cache without that chip, so keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("d", [256, 602])
@pytest.mark.parametrize("bits", [1, 2, 4, 8])
def test_quantize_pack_compiles_for_v5e(one_chip, bits, d):
    h = _spec((ROWS, d), jnp.float32, one_chip)
    compiled = quantize_pack.lower(h, h, bits=bits, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("d", [256, 602])
@pytest.mark.parametrize("bits", [1, 2, 4, 8])
def test_unpack_dequantize_compiles_for_v5e(one_chip, bits, d):
    packed = _spec((ROWS, packed_width(d, bits)), jnp.uint8, one_chip)
    row = _spec((ROWS,), jnp.float32, one_chip)
    compiled = unpack_dequantize.lower(packed, row, row, bits=bits, d=d,
                                       interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
