"""The main path's device programs compile for a TPU v5e, without a chip.

Compiles for a described v5e:2x2 (``jax.experimental.topologies``): the
served packed step and the batched apply on both kernel lanes at
1024 docs x 256 slots x 64 ops, the 4-chip doc-mesh packed step at
8192 x 256 x 64, the long tier, the grid cell's cells step, in place,
a base's page read of its store, and the farm's tile steps at the grid
cell's 1,200 rows, in place. What the chip's compiler would
refuse (a Mosaic kernel that does not tile, a program past the chip's
16 GB) fails here at no chip time. Nothing runs: a compile that passes
is not a chip run.

The topology is described inside a module fixture, never at import: one
process at a time may load libtpu, and each xdist worker imports every
test file. Keep these tests in this one file.
"""

from __future__ import annotations

import pytest

D, S, K = 1024, 256, 64
MESH_D = 8192
HBM_BYTES = 16 * 10**9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        # libtpu's compiler logs would otherwise land under /tmp
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies

        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "no v5e"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _shapes(tree, sharding):
    import jax

    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _state_shapes(n_docs: int, sharding):
    import jax
    import jax.numpy as jnp

    from fluidframework_tpu.ops.doc_state import DocState

    return _shapes(jax.eval_shape(
        lambda: jax.vmap(lambda _: DocState.empty(S))(jnp.arange(n_docs))),
        sharding)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _packed_wave(n_docs: int, sharding):
    import jax
    import jax.numpy as jnp

    from fluidframework_tpu.ops.apply import OP_FIELDS

    return (jax.ShapeDtypeStruct((n_docs, K, OP_FIELDS), jnp.int16,
                                 sharding=sharding),
            jax.ShapeDtypeStruct((n_docs, 2), jnp.int32, sharding=sharding))


@pytest.mark.parametrize("lane", ["xla", "pallas"])
def test_served_packed_step_compiles(topo, no_compile_cache, lane):
    """The applier's dense step (int16 wave unpack + apply + zamboni)."""
    from jax.sharding import SingleDeviceSharding

    from fluidframework_tpu.service.tpu_applier import _dense_step_for

    one = SingleDeviceSharding(topo.devices[0])
    packed_fn, _wide = _dense_step_for(D, K, use_pallas=lane == "pallas")
    compiled = packed_fn.lower(_state_shapes(D, one),
                               *_packed_wave(D, one)).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == (lane == "pallas")
    assert _device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("lane", ["xla", "pallas"])
def test_batched_apply_compiles(topo, no_compile_cache, lane):
    """The kernel alone on an int32 wave, with the fused zamboni."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from fluidframework_tpu.ops.apply import (OP_FIELDS, apply_ops_batch,
                                              compact_batch, wave_min_seq)
    from fluidframework_tpu.ops.pallas_apply import pallas_apply_ops_batch

    apply_fn = pallas_apply_ops_batch if lane == "pallas" else apply_ops_batch

    def step(state, ops):
        return compact_batch(apply_fn(state, ops), wave_min_seq(ops))

    one = SingleDeviceSharding(topo.devices[0])
    ops = jax.ShapeDtypeStruct((D, K, OP_FIELDS), jnp.int32, sharding=one)
    compiled = jax.jit(step).lower(_state_shapes(D, one), ops).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == (lane == "pallas")
    assert _device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("lane", ["xla", "pallas"])
def test_mesh_packed_step_compiles(topo, no_compile_cache, lane):
    """The doc-mesh lane's packed step over four chips, 2048 docs each:
    per-device bytes fit one chip, and the stats psum is an all-reduce."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from fluidframework_tpu.parallel.mesh import make_mesh
    from fluidframework_tpu.parallel.sharded_apply import (
        make_sharded_packed_step)

    mesh = make_mesh(devices=list(topo.devices)[:4])
    docs = NamedSharding(mesh, P("docs"))
    packed_fn, _wide = make_sharded_packed_step(
        mesh, donate=True, use_pallas=lane == "pallas")
    compiled = packed_fn.lower(_state_shapes(MESH_D, docs),
                               *_packed_wave(MESH_D, docs)).compile()
    hlo = compiled.as_text()
    assert ("tpu_custom_call" in hlo) == (lane == "pallas")
    assert "all-reduce" in hlo
    assert _device_bytes(compiled) < HBM_BYTES


LONG_L, LONG_S = 16, 8192


@pytest.mark.parametrize("lane", ["xla", "pallas"])
def test_long_tier_step_compiles(topo, no_compile_cache, lane):
    """The long tier's step at 16 x 8192 slots x 32 ops (int32 wave,
    apply at the tier's layout, zamboni): the Pallas kernel fits its
    VMEM scope, and is the custom call."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from fluidframework_tpu.ops.apply import OP_FIELDS
    from fluidframework_tpu.ops.doc_state import DocState
    from fluidframework_tpu.service.tpu_applier import _long_step_for

    one = SingleDeviceSharding(topo.devices[0])
    step = _long_step_for(LONG_L, 32, use_pallas=lane == "pallas")
    state = _shapes(jax.eval_shape(lambda: jax.vmap(
        lambda _: DocState.empty(LONG_S))(jnp.arange(LONG_L))), one)
    wave = jax.ShapeDtypeStruct((LONG_L, 32, OP_FIELDS), jnp.int32,
                                sharding=one)
    compiled = step.lower(state, wave).compile()
    hlo = compiled.as_text()
    assert ("tpu_custom_call" in hlo) == (lane == "pallas")
    assert _device_bytes(compiled) < HBM_BYTES


def test_promotion_compiles(topo, no_compile_cache):
    """A farm row of the text cells' geometry moved into a long row."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from fluidframework_tpu.ops.doc_state import DocState
    from fluidframework_tpu.service.tpu_applier import _promote_rows

    one = SingleDeviceSharding(topo.devices[0])
    long = _shapes(jax.eval_shape(lambda: jax.vmap(
        lambda _: DocState.empty(LONG_S))(jnp.arange(LONG_L))), one)
    idx = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
    compiled = jax.jit(_promote_rows).lower(
        _state_shapes(D, one), long, idx, idx).compile()
    assert _device_bytes(compiled) < HBM_BYTES


GRID_G, GRID_H, GRID_D = 100, 1536, 1200


def _grid_shapes(topo):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(topo.devices[0])

    def sd(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    store = (sd((GRID_G, GRID_H, GRID_H)),
             sd((GRID_G, GRID_H, GRID_H), jnp.int8))
    return one, sd, store


def test_matrix_cells_compiles_in_place(topo, no_compile_cache):
    """The grid cell's cells step: a [100, 1536, 1536] store and its
    int8 marks beside the [1200, 256] farm, 32 setCells a grid. Both are
    donated and written in place: the step's scratch stays far below one
    copy of the store (0.94 GB), which a scatter that flattens it would
    take."""
    import jax

    from fluidframework_tpu.ops.matrix_cells import (CELL_FIELDS,
                                                      matrix_cells)

    one, sd, store = _grid_shapes(topo)
    compiled = jax.jit(matrix_cells, donate_argnums=(0, 1)).lower(
        *store, _state_shapes(GRID_D, one),
        sd((GRID_G,)), sd((GRID_G,)), sd((GRID_G, 32, CELL_FIELDS))
    ).compile()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes == GRID_G * GRID_H * GRID_H * 5
    assert m.temp_size_in_bytes < 64 * 2**20
    assert _device_bytes(compiled) < HBM_BYTES


def test_written_cells_reads_a_page_without_copying_the_store(
        topo, no_compile_cache):
    """A base's page read of the same store: its scratch is one plane's
    ranks and the page, not a copy of the store or of its marks."""
    from functools import partial

    import jax

    from fluidframework_tpu.ops.matrix_cells import written_cells

    _one, sd, store = _grid_shapes(topo)
    compiled = jax.jit(partial(written_cells, page=4096)).lower(
        *store, sd((GRID_G,))).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20
    assert _device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("tile", [80, 304])
@pytest.mark.parametrize("lane", ["xla", "pallas"])
def test_tile_step_compiles_in_place(topo, no_compile_cache, lane, tile):
    """The farm's tile step at the grid cell's [1200, 256] farm and 32
    ops a row: the tile's rows gathered, stepped and written back into
    the donated farm in place. Its scratch is the full step's at T rows
    and the tile's own rows, far below one copy of the farm."""
    import jax
    import jax.numpy as jnp

    from fluidframework_tpu.ops.apply import OP_FIELDS
    from fluidframework_tpu.service.tpu_applier import (_dense_step_for,
                                                        _tile_sizes,
                                                        _tile_step_for)

    assert tile in _tile_sizes(GRID_D)
    one, sd, _store = _grid_shapes(topo)
    pallas = lane == "pallas"
    wave = (sd((tile, 32, OP_FIELDS), jnp.int16), sd((tile, 2)))

    def compiled(fn, n_rows, *args):
        return jax.jit(fn.__wrapped__, donate_argnums=(0,)).lower(
            _state_shapes(n_rows, one), *args, *wave).compile()

    farm = sum(a.size * a.dtype.itemsize
               for a in jax.tree.leaves(_state_shapes(GRID_D, one)))
    step = compiled(_tile_step_for(32, use_pallas=pallas), GRID_D,
                    sd((tile,)))
    alone = compiled(_dense_step_for(tile, 32, use_pallas=pallas)[0], tile)
    assert ("tpu_custom_call" in step.as_text()) == pallas
    m = step.memory_analysis()
    # every farm array aliased (the device pads the per-row ones)
    assert m.alias_size_in_bytes >= farm
    assert m.temp_size_in_bytes < (
        alone.memory_analysis().temp_size_in_bytes + farm // 2)
    assert _device_bytes(step) < HBM_BYTES
