"""Compile the main-path kernels for a described TPU v5e (nothing runs).

Interpret mode cannot see what the chip's compiler refuses: a block that is
not aligned to the (8, 128) tiling, a kernel that needs more VMEM than the
scoped limit, or a program that copies a donated pool instead of aliasing
it.  These tests compile each main-path kernel at real widths for a v5e
described by JAX's topology API, with the CPU backend still the default.

The topology is described inside a module-scoped fixture, never at import
time: only one process at a time may load the TPU library, and under a
multi-worker pytest run every worker imports this file.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import migrator
from repro.core.state import LeapState, flat_pool_view
from repro.kernels import ops
from repro.kernels.heat_scan import padded_heat_len

# Granite-3.0-2B KV page: [layers, K/V, block_tokens, kv_heads * head_dim]
GRANITE_PAGE = (40, 2, 16, 8 * 64)
SMALL_BLOCK = (16, 1024)  # 64 KiB of f32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip cannot be read back from the
    # persistent cache without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, **kwargs):
    compiled = jax.jit(fn, **kwargs).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize(
    "block, dtype", [(SMALL_BLOCK, jnp.float32), (GRANITE_PAGE, jnp.bfloat16)]
)
def test_copy_blocks_compiles(one_chip, block, dtype):
    pool = _sds((64,) + block, dtype, one_chip)
    idx = _sds((16,), jnp.int32, one_chip)
    compiled = _compile(
        lambda p, s, d: ops.copy_blocks_impl(p, s, d, impl="pallas"),
        pool, idx, idx, donate_argnums=(0,),
    )
    assert compiled.memory_analysis().temp_size_in_bytes < np.prod(block) * 4


def test_copy_runs_compiles_at_kv_page(one_chip):
    pool = _sds((64,) + GRANITE_PAGE, jnp.bfloat16, one_chip)
    starts = _sds((4,), jnp.int32, one_chip)
    _compile(
        lambda p, s, d: ops.copy_runs_impl(p, s, d, run=8, impl="pallas"),
        pool, starts, starts, donate_argnums=(0,),
    )


def test_heat_scan_compiles(one_chip):
    heat = _sds((padded_heat_len(65_536),), jnp.float32, one_chip)
    ids = _sds((4096,), jnp.int32, one_chip)
    w = _sds((4096,), jnp.float32, one_chip)
    _compile(
        lambda h, i, ww: ops.heat_scan_impl(h, i, ww, 0.9, impl="pallas"),
        heat, ids, w, donate_argnums=(0,),
    )


# (batch, pages per table, pool slots): a small batch, and the shape of the
# granite.decode_rebalance cell (32 sequences, 4,096 positions, 2 x 2,048 slots)
PAGED_SHAPES = [(8, 72, 512), (32, 256, 4096)]


@pytest.mark.parametrize("shape", PAGED_SHAPES, ids=["small", "cell"])
def test_paged_decode_compiles_at_granite_widths(one_chip, shape):
    _compile_paged_decode(one_chip, shape, scale=None)


@pytest.mark.parametrize("shape", PAGED_SHAPES, ids=["small", "cell"])
def test_paged_decode_compiles_at_granite_attention_multiplier(one_chip, shape):
    """Granite scores q.k / 64 (``attention_multiplier``), not q.k / 8; the
    scale is static in the kernel, so this is a program of its own."""
    _compile_paged_decode(one_chip, shape, scale=1 / 64)


def _compile_paged_decode(one_chip, shape, scale):
    """Compiles within the default scoped VMEM: no limit is raised."""
    b, maxb, slots = shape
    kvh, g, hd = 8, 4, 64
    pool = _sds((slots,) + GRANITE_PAGE, jnp.bfloat16, one_chip)
    q = _sds((b, kvh * g, hd), jnp.bfloat16, one_chip)
    tables = _sds((b, maxb), jnp.int32, one_chip)
    lens = _sds((b,), jnp.int32, one_chip)
    _compile(
        lambda q, p, t, ln: ops.paged_decode_partial(
            q, p, t, ln, kv_heads=kvh, layer=39, scale=scale, impl="pallas"
        ),
        q, pool, tables, lens,
    )


def test_megastep_keeps_donated_kv_pool_aliased(one_chip):
    """The steady-state tick (commit, begin, copy), its index operands packed
    into one vector, on a 2 x 256-page Granite KV pool must alias the
    donated pool: temp well under the pool's bytes."""
    n_regions, slots, n_blocks, bucket = 2, 256, 256, 64
    pool = _sds((n_regions, slots) + GRANITE_PAGE, jnp.bfloat16, one_chip)
    state = LeapState(
        pool=pool,
        table=_sds((n_blocks, 2), jnp.int32, one_chip),
        dirty=_sds((n_blocks,), jnp.bool_, one_chip),
        in_flight=_sds((n_blocks,), jnp.bool_, one_chip),
    )
    lane = np.zeros(bucket, np.int32)
    packed, layout = migrator.pack_operands(
        {
            "commit_ids": lane, "commit_regions": lane, "commit_slots": lane,
            "begin_ids": lane,
            "copy_src": lane, "copy_dst": lane,
        }
    )
    lowered = migrator.megastep.lower(
        state,
        _sds(packed.shape, jnp.int32, one_chip),
        layout=layout,
        group=1,
        impl="pallas",
    )
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    pool_bytes = int(np.prod(pool.shape)) * 2
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 8


def test_flat_pool_view_keeps_the_payload():
    pool = jnp.zeros((2, 3) + (4, 2, 5), jnp.float32)
    assert flat_pool_view(pool).shape == (6, 4, 2, 5)


def test_ppermute_copy_compiles_on_a_2x2_mesh(topo):
    """Region-per-chip copy: the Pallas gather/scatter run inside shard_map
    around one collective permute."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import PoolConfig
    from repro.core.state import state_sharding

    mesh = Mesh(np.array(topo.devices[:4]), ("region",),
                axis_types=(jax.sharding.AxisType.Auto,))
    cfg = PoolConfig(4, 64, SMALL_BLOCK, jnp.float32, region_axis="region")
    sh = state_sharding(cfg, mesh)
    n = 32
    state = LeapState(
        pool=jax.ShapeDtypeStruct((4, 64) + SMALL_BLOCK, jnp.float32, sharding=sh.pool),
        table=jax.ShapeDtypeStruct((n, 2), jnp.int32, sharding=sh.table),
        dirty=jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=sh.dirty),
        in_flight=jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=sh.in_flight),
    )
    slots = jax.ShapeDtypeStruct((16,), jnp.int32, sharding=NamedSharding(mesh, P()))
    compiled = migrator.fused_copy_ppermute.lower(
        state, slots, slots, 0, 2, "region", mesh, impl="pallas"
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "collective-permute" in text
