"""Ahead-of-time compiles of the main-path kernels for a described TPU.

Interpret mode (what every other test runs) accepts block shapes, VMEM
footprints and operand layouts that the TPU's compiler refuses.  These
tests compile the served path's kernels — and one whole pallas tick
program — for a ``v5e:2x2`` topology that is described, not attached,
at the shapes of the large tenant ``chip_smoke.py`` serves: a
317,080-node graph in the 524,288-node class whose ~2.1M half-edges
fill 32,768 chunks of 128, with k = 6 panels.

The topology is described inside a module-scoped fixture (never at
import time), so every pytest-xdist worker collects the same tests and
only the worker that runs this file loads the TPU compiler.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import backend as backend_mod
from repro.core import program
from repro.kernels.edge_spmm import ops as es_ops
from repro.kernels.eg_update import kernel as eg_kernel

K = 6  # ServiceConfig.k
NODE_CAP = 524_288  # node class of the smoke's large tenant
NUM_CHUNKS = 32_768  # its pow2-snapped chunk count at block_e = 128
BLOCK_N, BLOCK_E = 512, 128  # ServiceConfig.tick_block_n, kernel chunk
HBM_BYTES = 16 * 2**30  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _compile(fn, *args, **kwargs):
    return jax.jit(fn, **kwargs).lower(*args).compile()


def test_edge_spmm_one_hot_compiles_at_node_limit(spec):
    n, e = backend_mod.ONE_HOT_NODE_LIMIT, 8192
    compiled = _compile(
        lambda s, d, w, v, a, b: es_ops.edge_spmm(
            s, d, w, v, a, b, interpret=False),
        spec((e,), jnp.int32), spec((e,), jnp.int32), spec((e,)),
        spec((n, K)), spec(()), spec(()))
    assert "tpu_custom_call" in compiled.as_text()


def test_edge_spmm_node_blocked_compiles_at_large_tenant(spec):
    slots = NUM_CHUNKS * BLOCK_E
    compiled = _compile(
        lambda ul, ot, w, cb, deg, v, ab: es_ops._edge_spmm_blocked(
            ul, ot, w, cb, deg, v, ab, block_n=BLOCK_N, block_e=BLOCK_E,
            num_chunks=NUM_CHUNKS, interpret=False),
        spec((slots,), jnp.int32), spec((slots,), jnp.int32),
        spec((slots,)), spec((NUM_CHUNKS + 1,), jnp.int32),
        spec((NODE_CAP,)), spec((NODE_CAP, K)), spec((2,)))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kernel", ["gram2k", "panel_mix"])
def test_eg_update_kernels_compile_at_large_tenant(spec, kernel):
    kp = 128  # eg_update.ops pads k to the lane width
    v = spec((NODE_CAP, kp))
    if kernel == "gram2k":
        compiled = _compile(eg_kernel.gram2k, v, v)
    else:
        m = spec((kp, kp))
        compiled = _compile(eg_kernel.panel_mix, v, v, m, m, spec((kp,)))
    assert "tpu_custom_call" in compiled.as_text()


def test_pallas_tick_program_fits_one_chip(spec, monkeypatch):
    # build_tick_program asks kernel_interpret() (True off-TPU); the
    # described chip needs the compiled kernels
    monkeypatch.setattr(backend_mod, "kernel_interpret", lambda: False)
    schedule = program.StepSchedule(method="mu_eg", degree=15, steps=20,
                                    backend="pallas")
    tick = program.build_tick_program(
        schedule, layout=(BLOCK_N, NUM_CHUNKS, BLOCK_E))
    slots = NUM_CHUNKS * BLOCK_E
    compiled = tick.lower(
        spec((1, slots), jnp.int32), spec((1, slots), jnp.int32),
        spec((1, slots)), spec((1, NUM_CHUNKS + 1), jnp.int32),
        spec((1, NODE_CAP)), spec((1, NODE_CAP, K)), spec((1,)),
        spec((1,)), spec((1,), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, mem
    assert "tpu_custom_call" in compiled.as_text()
