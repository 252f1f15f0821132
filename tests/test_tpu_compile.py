"""The served Pallas kernels compile for a TPU v5e chip at the KV planes
of the benchmark's configurations (qwen3-1.7b: 28 layers x 1024
features; minicpm-2b: 40 layers x 2304), with no chip attached: the TPU compiler is installed and compiles
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

GROUP = 64                                               # KIVICompression
# (config, tokens of the entry compiled, id prefix): qwen3-1.7b's cases
# keep their first ids; minicpm-2b's entry is one 256-token page
PLANES = [("qwen3-1.7b", 512, ""), ("minicpm-2b", 256, "minicpm-2b-")]


def _plane(name: str):
    """(layers, K/V features per token) of a configuration."""
    cfg = get_config(name)
    return cfg.n_layers, cfg.n_kv_heads * cfg.resolved_head_dim


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


@pytest.mark.parametrize("name, tokens, style, bits", [
    pytest.param(n, t, style, bits, id=f"{pre}{style}-{bits}")
    for n, t, pre in PLANES for style in "kv" for bits in (2, 4, 8)])
def test_kivi_compiles_for_v5e(one_chip, name, tokens, style, bits):
    """An entry of all layers (qwen3-1.7b: 512 tokens x 28 layers;
    minicpm-2b: a 256-token page x 40 layers). K groups tokens per
    channel; V groups channels per token, which ops.py runs as the
    transpose."""
    layers, width = _plane(name)
    rows = layers * tokens
    shape = (rows, width) if style == "k" else (width, rows)
    _compile_roundtrip(one_chip, *shape, bits, GROUP)


@pytest.mark.parametrize("name, bits", [
    pytest.param(n, bits, id=f"{pre}{bits}")
    for n, _, pre in PLANES for bits in (2, 4, 8)])
def test_kivi_compiles_short_entry_group(one_chip, name, bits):
    """A one-token remainder entry: as many K rows as layers (28 or 40),
    so the compressor picks a group of that many (not a multiple of 8
    sublanes)."""
    layers, width = _plane(name)
    g = _round_group(min(GROUP, layers), bits)
    _compile_roundtrip(one_chip, layers, width, bits, g)
