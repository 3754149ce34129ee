"""Ahead-of-time compiles of the main-path kernels for a described TPU v5e.

Interpret mode (every other kernel test) never sees Mosaic's layout rules;
these compiles do, at the real widths of the KDD-Cup deployment (n =
311,029 points padded to the tile, d = 74, k = 512 centers, 24 tree code
planes, 15 LSH tables, 1024 candidates).  Nothing runs: the chip's
compiler is asked for each kernel and must emit a Mosaic custom call.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and under pytest-xdist every
worker imports this module.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.batch_schedule import BatchSchedule
from repro.core.device_seeding import device_rejection_sampling
from repro.kernels import ops

N, D, K, H, L, B = 311_029, 74, 512, 24, 15, 1024
TILE = 512


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it.
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


def _assert_mosaic(compiled):
    assert "tpu_custom_call" in compiled.as_text()


F32, I32 = jnp.float32, jnp.int32

KERNELS = {
    "pairwise_argmin": (
        functools.partial(ops.pairwise_argmin, interpret=False),
        [((N, D), F32), ((K, D), F32)]),
    "d2_update": (
        functools.partial(ops.d2_update, interpret=False),
        [((N, D), F32), ((D,), F32), ((N,), F32)]),
    "d2_update_tiles": (
        functools.partial(ops.d2_update_tiles, interpret=False),
        [((N, D), F32), ((D,), F32), ((N,), F32)]),
    "tree_sep_update": (
        functools.partial(ops.tree_sep_update, scale=100.0,
                          num_levels=H + 1, block_n=TILE, interpret=False),
        [((H, N), I32), ((H, N), I32), ((H,), I32), ((H,), I32),
         ((N,), F32)]),
    "tree_sep_update_tiles": (
        functools.partial(ops.tree_sep_update_tiles, scale=100.0,
                          num_levels=H + 1, block_n=TILE, interpret=False),
        [((H, N), I32), ((H, N), I32), ((H,), I32), ((H,), I32),
         ((N,), F32)]),
    "lsh_bucket_min": (
        functools.partial(ops.lsh_bucket_min, interpret=False),
        [((L, B), I32), ((L, B), I32), ((B, D), F32), ((L, K), I32),
         ((L, K), I32), ((K, D), F32)]),
    "lsh_bucket_accept": (
        functools.partial(ops.lsh_bucket_accept, c2=4.0, interpret=False),
        [((L, B), I32), ((L, B), I32), ((B, D), F32), ((L, K), I32),
         ((L, K), I32), ((K, D), F32), ((B,), F32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = KERNELS[name]
    _assert_mosaic(_compile(fn, one_chip, *shapes))


def test_rejection_program_compiles_for_v5e(one_chip):
    """The whole Algorithm-4 device program, as the `device` backend runs
    it (adaptive candidate schedule, all bucket branches)."""
    fn = functools.partial(
        device_rejection_sampling, k=25, scale=100.0, num_levels=H + 1,
        m_init=1e6, c=2.0, schedule=BatchSchedule(), max_rounds=32,
        tile=TILE, interpret=False)
    key = jax.random.key_data(jax.random.key(0))
    compiled = _compile(
        lambda cl, ch, p, kl, kh, bits: fn(
            cl, ch, p, kl, kh, key=jax.random.wrap_key_data(bits)),
        one_chip, ((3, H, N), I32), ((3, H, N), I32), ((N, D), F32),
        ((L, N), I32), ((L, N), I32), (key.shape, key.dtype))
    _assert_mosaic(compiled)
