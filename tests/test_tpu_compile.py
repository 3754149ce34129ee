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
def topo():
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
    yield topo
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


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


# The four-chip deployment: 24,582,850 x 68 (Census width, 10x its rows),
# k = 500, rejection/sharded over the described 2x2 host's 1 x 4 mesh.
CENSUS_N, CENSUS_D, CENSUS_K, CENSUS_H = 24_582_850, 68, 500, 14


@pytest.fixture(scope="module")
def four_chips(topo):
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.array(topo.devices), ("data",))


def _spec(mesh, shape, dtype, *axes):
    from jax.sharding import NamedSharding, PartitionSpec

    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(
                                    mesh, PartitionSpec(*axes)))


def test_sharded_rejection_program_fits_each_chip(four_chips):
    """The sharded Algorithm-4 program at the four-chip deployment's size:
    each chip holds its quarter of the codes, keys and coordinate planes
    (800 B a row with the TPU's tile padding, 4.92 GB) and the working set
    of a seeding (the shard's padded code planes, 2.25 GB, and the rest),
    under 8 GB of its 16: a whole copy of the set, or a relayout of a
    shard's coordinates for the candidate gathers, would not fit."""
    from repro.core import sharded_seeding as ss

    mesh = four_chips
    n_pad = -(-CENSUS_N // (4 * TILE)) * 4 * TILE
    fn = ss._rejection_program(
        mesh, 3, CENSUS_H, n_pad, L, 72, CENSUS_K, 1000.0, CENSUS_H + 1,
        1e8, CENSUS_N, 2.0, BatchSchedule(), 32, TILE, False)
    key = jax.random.key_data(jax.random.key(0))
    compiled = fn.lower(
        _spec(mesh, (3, CENSUS_H, n_pad), I32, None, None, "data"),
        _spec(mesh, (3, CENSUS_H, n_pad), I32, None, None, "data"),
        _spec(mesh, (72, n_pad), F32, None, "data"),
        _spec(mesh, (L, n_pad), I32, None, "data"),
        _spec(mesh, (L, n_pad), I32, None, "data"),
        _spec(mesh, key.shape, key.dtype)).compile()
    _assert_mosaic(compiled)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < 5.0e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 8.0e9


def test_sharded_finish_reads_the_rows_in_place(four_chips):
    """The finish's center gather and blocked cost over the rows placed
    as coordinate planes (1.77 GB a chip) need no copy of them."""
    from repro.core import sharded_seeding as ss

    mesh = four_chips
    per_chip = -(-CENSUS_N // 4)
    per_chip = -(-per_chip // ss.COST_BLOCK_ROWS) * ss.COST_BLOCK_ROWS
    shape = (72, 4 * per_chip)
    rows = _spec(mesh, shape, F32, None, "data")
    take = ss._take_program(mesh, shape, CENSUS_D).lower(
        rows, _spec(mesh, (CENSUS_K,), I32)).compile()
    cost = ss._rows_cost_program(
        mesh, shape, CENSUS_N, CENSUS_D, ss.COST_BLOCK_ROWS).lower(
        rows, _spec(mesh, (CENSUS_K, CENSUS_D), F32)).compile()
    for compiled in (take, cost):
        mem = compiled.memory_analysis()
        assert mem.argument_size_in_bytes < 1.8e9
        assert mem.temp_size_in_bytes < 0.2e9


def test_device_hash_of_a_shard_compiles_for_v5e(four_chips):
    """The trees' hash of the four-chip deployment as
    `prepare_rejection_sharded` runs it: one program over the mesh, each
    chip hashing its own 6,146,048 rows x 3 trees x 68 uint16 cells
    (uint64 arithmetic, which the TPU emulates) a chunk of rows at a time,
    with no collective.  Cells, temporaries and codes together stay under
    6 GB a chip, so with the shard's keys and coordinates (2.6 GB) the
    prepare's peak is under 9 GB of the chip's 16."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.core import sharded_seeding as ss

    mesh = four_chips
    n_pad = -(-CENSUS_N // (4 * TILE)) * 4 * TILE
    fn = ss._hash_program(mesh, n_pad, CENSUS_N, CENSUS_H + 1,
                          ss.HASH_CHUNK_ROWS)
    with jax.enable_x64(True):
        compiled = fn.lower(
            _spec(mesh, (3, n_pad, CENSUS_D), jnp.uint16,
                  None, "data", None),
            jax.ShapeDtypeStruct((3, 1, CENSUS_D), jnp.uint64,
                                 sharding=NamedSharding(
                                     mesh, PartitionSpec()))).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes)
    assert total < 6.0e9, total
    hlo = compiled.as_text()
    assert not any(op in hlo for op in ("all-reduce", "all-gather",
                                        "collective-permute", "all-to-all"))
    assert np.dtype("uint16") == np.min_scalar_type(2 ** CENSUS_H - 1)
