"""Describe-only TPU v5e compiles of the drain's main path.

Nothing here runs on a chip: each case lowers and compiles a jitted
program for a v5e that is described (``topologies.get_topology_desc``)
and not attached, so the TPU compiler refuses here what it would refuse
on the chip. Covered: the fused chunk runner at the paper and DarkNet
mesh sizes, batched and not; the router step's FIFO reads and writes
(no gather or scatter but the injection read from the wire); the O3
chain at a DarkNet layer's window stack; and the variants-sharded runner
on a 4-chip mesh. The router
kernel's guard is pinned too: Mosaic rejects its gathers, so asking for
a TPU build raises a clear ValueError.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

from repro.kernels.min_hamming import DEFAULT_BEAM, DEFAULT_STARTS, \
    _chain_stack
from repro.kernels.router_step import TPU_UNSUPPORTED, make_router_step
from repro.noc import mesh_by_name
from repro.noc.sim import (Wire, _chunk_runner, _mesh_key,
                           _sharded_chunk_runner, make_state)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # A described chip's compile is written to the persistent cache but
    # cannot be read back without a chip: keep the cache out of it.
    prior = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no compiler
        jax.config.update("jax_enable_compilation_cache", prior)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", prior)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _drain_args(cfg, m, t, batch, sharding):
    """Shapes (with ``sharding``) of one drain's (state, wire, mc_nodes)."""
    lead = (batch,) if batch else ()
    state = jax.eval_shape(lambda: make_state(cfg, m))
    lf = cfg.lanes + 1

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(lead + tuple(shape), dtype,
                                    sharding=sharding)
    return (jax.tree.map(lambda s: spec(s.shape, s.dtype), state),
            Wire(spec((m, t, lf), jnp.uint32), spec((m,), jnp.int32)),
            spec((m,), jnp.int32))


# (mesh, flits per MC stream, chunk cycles): the paper meshes and the full
# DarkNet mesh, whose interleaved streams run ~82k flits.
DRAIN_CASES = [("4x4_mc2", 8192, 512), ("8x8_mc4", 8192, 512),
               ("16x16_mc16", 81920, 4096)]


@pytest.mark.parametrize("batch", [0, 8])
@pytest.mark.parametrize("mesh,t,chunk", DRAIN_CASES)
def test_fused_chunk_runner_compiles_for_v5e(one_chip, mesh, t, chunk,
                                             batch):
    cfg = mesh_by_name(mesh)
    run = _chunk_runner(_mesh_key(cfg), True, chunk, bool(batch), False,
                        "fused")
    args = _drain_args(cfg, cfg.num_mcs, t, batch, one_chip)
    compiled = run.lower(*args).compile()
    mem = compiled.memory_analysis()
    if mem is not None:      # a v5e chip holds 16 GB of HBM
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_untracked_step_has_no_fifo_gather_or_scatter(one_chip):
    """The benchmark's drain (8x8/MC8, 3 lanes, 2,048-cycle chunks) holds
    one gather, the per-stream injection read whose operand is the wire,
    and no scatter: the FIFO, head and count state are read and written
    by one-hot selects. On a v5e each serialized gather or scatter there
    cost several us a cycle."""
    cfg = mesh_by_name("8x8_mc8")
    batch, t = 3, 8192
    run = _chunk_runner(_mesh_key(cfg), True, 2048, True, False, "fused")
    text = run.lower(*_drain_args(cfg, cfg.num_mcs, t, batch,
                                  one_chip)).compile().as_text()
    shapes = dict(re.findall(r"(%[\w.-]+) = (\w+\[[\d,]*\])", text))
    ops = re.findall(r"(%[\w.-]+) = \S+ (gather|scatter)\((%[\w.-]+)"
                     r".*?op_name=\"([^\"]*)\"", text)
    assert len(ops) == len(re.findall(r" (?:gather|scatter)\(", text))
    wire = f"u32[{batch},{cfg.num_mcs},{t},{cfg.lanes + 1}]"
    found = [(kind, shapes[operand], op_name)
             for _, kind, operand, op_name in ops]
    assert [f[:2] for f in found] == [("gather", wire)], found


def test_chain_stack_compiles_for_v5e(one_chip):
    """O3's chain at a DarkNet layer's stack: (planes, windows, window)."""
    u = jax.ShapeDtypeStruct((2, 4096, 64), jnp.uint32, sharding=one_chip)
    _chain_stack.lower(u, DEFAULT_BEAM, DEFAULT_STARTS).compile()


def test_sharded_chunk_runner_compiles_on_four_chips(topo):
    """The variants axis over a 4-chip mesh: lanes never communicate, so
    the program must hold no collectives."""
    cfg = mesh_by_name("16x16_mc16")
    dev_mesh = Mesh(topo.devices[:4], ("variants",))
    sharded = NamedSharding(dev_mesh, PartitionSpec("variants"))
    run = _sharded_chunk_runner(_mesh_key(cfg), True, 4096, dev_mesh, False,
                                "fused")
    compiled = run.lower(*_drain_args(cfg, cfg.num_mcs, 81920, 4,
                                      sharded)).compile()
    text = compiled.as_text()
    for op in ("all-reduce", "all-gather", "all-to-all",
               "collective-permute", "reduce-scatter"):
        assert op not in text, op


@pytest.mark.parametrize("mesh", ["4x4_mc2", "16x16_mc16"])
def test_router_kernel_refuses_a_tpu_build(mesh):
    """``interpret=False`` is the Mosaic build: it raises the clear error
    instead of Mosaic's NotImplementedError deep inside the drain."""
    cfg = mesh_by_name(mesh)
    with pytest.raises(ValueError, match="does not compile for TPU") as e:
        make_router_step(_mesh_key(cfg), True, cfg.lanes + 1, cfg.num_mcs,
                         False)
    assert str(e.value) == TPU_UNSUPPORTED
