"""The served Pallas kernels compile for a TPU v5e chip at qwen3-1.7b
widths, with no chip attached: the TPU compiler is installed and compiles
for a described ``v5e:2x2`` topology. Interpret-mode tests cannot see
Mosaic's tiling rules; these do.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and pytest-xdist workers all
import this file."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.compression.kivi import _round_group
from repro.kernels.kivi import kernel as kk

CFG = get_config("qwen3-1.7b")
KV_WIDTH = CFG.n_kv_heads * CFG.resolved_head_dim        # 1024
GROUP = 64                                               # KIVICompression


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile_roundtrip(sharding, rows: int, cols: int, bits: int,
                       group: int) -> None:
    """Lower and compile quantize and dequantize of a (rows, cols) array
    grouped along rows, as the TPU compiler would for the chip."""
    cpb = 8 // bits
    x = jax.ShapeDtypeStruct((rows, cols), jnp.float32, sharding=sharding)
    jax.jit(lambda x: kk.quantize_pallas(x, bits, group, interpret=False)
            ).lower(x).compile()
    packed = jax.ShapeDtypeStruct((rows // cpb, cols), jnp.uint8,
                                  sharding=sharding)
    stat = jax.ShapeDtypeStruct((rows // group, cols), jnp.float32,
                                sharding=sharding)
    compiled = jax.jit(lambda p, s, z: kk.dequantize_pallas(
        p, s, z, bits, group, jnp.float32, interpret=False)
    ).lower(packed, stat, stat).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("style", ["k", "v"])
def test_kivi_compiles_for_v5e(one_chip, bits, style):
    """A 512-token entry of all 28 layers. K groups tokens per channel;
    V groups channels per token, which ops.py runs as the transpose."""
    rows = CFG.n_layers * 512
    shape = (rows, KV_WIDTH) if style == "k" else (KV_WIDTH, rows)
    _compile_roundtrip(one_chip, *shape, bits, GROUP)


@pytest.mark.parametrize("bits", [2, 4, 8])
def test_kivi_compiles_short_entry_group(one_chip, bits):
    """A one-token remainder entry: 28 K rows, so the compressor picks a
    group of 28 (not a multiple of 8 sublanes)."""
    g = _round_group(min(GROUP, CFG.n_layers), bits)
    _compile_roundtrip(one_chip, CFG.n_layers, KV_WIDTH, bits, g)
