"""Compile the main path's chunk updates for a TPU v5e that is described,
not attached.

The TPU compiler is installed wherever jax's TPU support is, and it
refuses what interpret mode accepts: a kernel over its scoped VMEM, a
slice not aligned to the tiling, a program larger than the device.
These tests compile the fused chunk updates at the paper's widths
(512-row chunks, k̃ = k + p = 60 + 910) and assert that each lowers to
a Mosaic kernel (``tpu_custom_call``).  Each compiles under the full-f32
matmul scope the fit runs its chunk updates in.  Nothing runs.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and pytest's workers each
import every test file.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.linalg import full_f32
from repro.kernels import ops

ROWS = 512
KT = 970  # k̃ = k + p of the paper's Europarl run at p = 910
D18, D19 = 1 << 18, 1 << 19
HBM_BYTES = 16 * 10**9  # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    compiled = jax.jit(full_f32(fn)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _chunk_shapes(d, kt):
    f32 = jnp.float32
    return ((ROWS, d), f32), ((ROWS, d), f32), ((d, kt), f32), ((d, kt), f32)


def test_power_pass_chunk_auto_fits_one_chip(one_chip):
    compiled = _compile(functools.partial(ops.power_pass_chunk, interpret=False),
                        one_chip, *_chunk_shapes(D18, KT))
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert total < HBM_BYTES, total


def test_final_pass_chunk_compiles(one_chip):
    _compile(functools.partial(ops.final_pass_chunk, interpret=False),
             one_chip, *_chunk_shapes(D18, KT))


def test_power_pass_chunk_seeded_staged_compiles(one_chip):
    fn = functools.partial(ops.power_pass_chunk_seeded, kt=KT,
                           q_dtype=jnp.float32, schedule="staged",
                           interpret=False)
    a, b, _, _ = _chunk_shapes(D18, KT)
    seed = ((2,), jnp.uint32)
    _compile(fn, one_chip, a, b, seed, seed)


def test_power_pass_chunk_recompute_at_paper_width_compiles(one_chip):
    """Mosaic refused this kernel under its default 16 MiB scoped VMEM
    limit; the launch now requests the limit its plan accounts for."""
    _compile(functools.partial(ops.power_pass_chunk, schedule="recompute",
                               interpret=False),
             one_chip, *_chunk_shapes(D19, KT))


def test_sharded_sweep_compiles(one_chip):
    """The collective-fused path's sweep on one chip's feature shard
    (d = 2^18 over two chips, k̃ = 256): 16.5 MiB of buffers, and its
    body's values need as much again."""
    _compile(functools.partial(ops.sweep_accumulate, interpret=False),
             one_chip, ((ROWS, D18 // 2), jnp.float32),
             ((ROWS, 256), jnp.float32))


def test_final_pass_chunk_wide_sketch_compiles(one_chip):
    """k̃ = 2060 (p = 2000): the C-column-bucketed projgram."""
    _compile(functools.partial(ops.final_pass_chunk, interpret=False),
             one_chip, *_chunk_shapes(D18, 2060))


def test_bf16_chunk_updates_compile(one_chip):
    """Mosaic refuses fp32 contract precision on bf16 operands, so the
    kernels keep those dots at one pass inside the full-f32 scope."""
    bf16 = jnp.bfloat16
    shapes = (((ROWS, 4096), bf16),) * 2 + (((4096, 256), bf16),) * 2
    for fn in (ops.power_pass_chunk, ops.final_pass_chunk):
        _compile(functools.partial(fn, interpret=False), one_chip, *shapes)
