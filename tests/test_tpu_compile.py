"""Compile the main path for a described TPU v5e (no chip needed).

The TPU compiler is installed with jaxlib and compiles for a topology
that is described, not attached: these tests catch what interpret mode
and the CPU backend cannot — a kernel block that breaks the (8, 128)
tiling rule, a daemon that does not partition over the mesh, state that
is not donated.  The topology is described inside a module fixture, so
importing this file never loads the TPU library, and every test skips
from the fixture where it cannot be described.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core import CollKind, OcclConfig, OcclRuntime, registered_heap_elems
from repro.core.daemon import (_relink_edges, _sim_daemon_jit,
                               build_shardmap_daemon, local_tables,
                               shared_tables)
from repro.core.state import init_state
from repro.core.tables import build_tables
from repro.kernels.chunk_combine import chunk_combine_pallas
from repro.kernels.fused_slice import fused_primitive_pallas


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _runtime(cfg, colls):
    """Register ``colls`` on a runtime whose heap fits them; returns the
    sized config and its static tables (no device state is built)."""
    def register(rt):
        comm = rt.communicator(list(range(cfg.n_ranks)))
        for kind, n in colls:
            rt.register(kind, comm, n_elems=n)

    cfg = dataclasses.replace(
        cfg, heap_elems=registered_heap_elems(cfg, register))
    rt = OcclRuntime(cfg)
    register(rt)
    return cfg, build_tables(cfg, rt.comms, rt.specs)


def _donated(compiled, state, devices: int = 1) -> bool:
    """The daemon state (its share on each of ``devices``) is aliased
    input -> output; the chip's tile padding only adds to the count."""
    nbytes = sum(a.size * a.dtype.itemsize
                 for a in jax.tree_util.tree_leaves(state))
    return compiled.memory_analysis().alias_size_in_bytes * devices >= nbytes


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_slice_kernel_compiles(one_chip, dtype):
    x = jax.ShapeDtypeStruct((8, 1024), dtype, sharding=one_chip)
    f = jax.ShapeDtypeStruct((8, 4), jnp.int32, sharding=one_chip)
    text = jax.jit(fused_primitive_pallas).lower(x, x, f).compile().as_text()
    assert "tpu_custom_call" in text


def test_chunk_combine_kernel_compiles(one_chip):
    x = jax.ShapeDtypeStruct((1 << 20,), jnp.float32, sharding=one_chip)
    text = jax.jit(chunk_combine_pallas).lower(x, x).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("use_pallas", [False, True])
def test_sim_daemon_compiles_on_one_chip(one_chip, use_pallas):
    cfg, t = _runtime(
        OcclConfig(n_ranks=4, max_colls=4, max_comms=1, slice_elems=8192,
                   burst_slices=8, conn_depth=24, use_pallas=use_pallas),
        [(CollKind.ALL_REDUCE, 1 << 20)])
    st = jax.eval_shape(lambda: init_state(cfg, per_rank=True))
    args = (shared_tables(t), local_tables(t), t.fwd_src, t.rev_src, st)
    compiled = _sim_daemon_jit(cfg, _relink_edges(t)).lower(
        *_spec(args, one_chip)).compile()
    assert _donated(compiled, st)
    assert ("tpu_custom_call" in compiled.as_text()) == use_pallas


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_shardmap_daemon_compiles_on_2x2(topo, dtype):
    mesh = Mesh(np.array(topo.devices), ("rank",))
    cfg, t = _runtime(
        OcclConfig(n_ranks=4, max_colls=4, max_comms=1, slice_elems=8192,
                   burst_slices=8, conn_depth=24, dtype=dtype),
        [(CollKind.ALL_REDUCE, 1 << 20), (CollKind.ALL_TO_ALL, 1 << 20)])
    st = jax.eval_shape(lambda: init_state(cfg, per_rank=True))
    compiled = build_shardmap_daemon(cfg, t, mesh).lower(
        _spec(st, NamedSharding(mesh, P("rank")))).compile()
    assert _donated(compiled, st, devices=4)
    assert "collective-permute" in compiled.as_text()
