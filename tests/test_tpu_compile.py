"""Compile the device programs of the main paths for a described TPU v5e.

Nothing runs: each test lowers and compiles a program at its real
widths for a chip that is described, not attached, which catches what
interpret mode cannot (block shapes the TPU tiling refuses, programs
that do not fit). The topology is described only inside the fixture,
never while a module is imported, so every test worker collects the
same tests.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_flash_attention_compiles_at_qwen3_widths(one_chip):
    from repro.kernels.flash_attention.ops import flash_attention
    q = _spec(one_chip, (1, 2048, 16, 128), jnp.bfloat16)
    kv = _spec(one_chip, (1, 2048, 8, 128), jnp.bfloat16)
    compiled = flash_attention.lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_rmsnorm_compiles_at_d1024(one_chip):
    from repro.kernels.rmsnorm.ops import fused_rmsnorm
    x = _spec(one_chip, (4096, 1024), jnp.bfloat16)
    w = _spec(one_chip, (1024,), jnp.bfloat16)
    compiled = fused_rmsnorm.lower(x, x, w).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_scan_compiles_at_zamba2_widths(one_chip):
    """64 heads, state 64, head_dim 64, chunk 128, seq 2048."""
    from repro.kernels.ssd_scan.ops import ssd_scan
    b, s, h, p, n = 1, 2048, 64, 64, 64
    compiled = ssd_scan.lower(
        _spec(one_chip, (b, s, h, p), jnp.bfloat16),
        _spec(one_chip, (b, s, n), jnp.bfloat16),
        _spec(one_chip, (b, s, n), jnp.bfloat16),
        _spec(one_chip, (b, s, h), jnp.float32),
        _spec(one_chip, (b, s, h), jnp.float32), chunk=128).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _sweep_specs(sharding, c, i, ranks):
    """The jitted sweep's arguments for ``ranks``, a (width, in-degree)
    per topological rank."""
    v = sum(w for w, _ in ranks)
    return (_spec(sharding, (i,), jnp.float64),
            _spec(sharding, (c, v), jnp.float64),
            tuple(_spec(sharding, r, jnp.int32) for r in ranks),
            tuple(_spec(sharding, r, jnp.bool_) for r in ranks))


def test_fleet_sweep_compiles_in_float64(one_chip):
    """The jax replay plane's rank sweep at C=64 candidates, I=4096
    instances, V=64 functions (a 62-wide fan-out's join)."""
    from repro.core.engine import _jax_sweep_fn
    c, i = 64, 4096
    with jax.enable_x64(True):
        compiled = _jax_sweep_fn().lower(*_sweep_specs(
            one_chip, c, i, [(1, 0), (62, 1), (1, 62)])).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == c * i * 8
    assert mem.temp_size_in_bytes < 16e9          # one v5e chip's HBM


def test_fleet_sweep_compiles_at_1000genome_width(one_chip):
    """The rank sweep of the 1000Genome workflow (22 chromosomes, 10
    shards each: 242 sources, 22 merges of 10 shards, 308 tasks after
    the merge and sifting) at C=8, I=4096: the (C, I, V) finish tensor
    is the program's largest temporary."""
    from repro.core.engine import _jax_sweep_fn
    c, i, v = 8, 4096, 572
    with jax.enable_x64(True):
        compiled = _jax_sweep_fn().lower(*_sweep_specs(
            one_chip, c, i, [(242, 0), (22, 10), (308, 2)])).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == c * i * 8
    assert c * i * v * 8 <= mem.temp_size_in_bytes < 16e9
