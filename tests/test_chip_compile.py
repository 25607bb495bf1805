"""The main path's Pallas kernels, compiled for a TPU v5e at the paper's
widths (``PAPER_CFG``: Bi-SRU, input 23, hidden 550 per direction, proj 256,
4 SRU layers, 1904 outputs) without a chip attached.

Interpret mode accepts block shapes and stores the TPU's compiler refuses;
these compiles run Mosaic itself on a described ``v5e:2x2`` topology, so a
kernel that would not compile on the chip fails here. Nothing runs: results
are the interpret-mode tests' business (``test_kernels.py``).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.sru_experiment import PAPER_CFG
from repro.kernels import ops

P = 16                  # population lanes (one search generation)
B, T = 32, 48           # sequences x frames per lane
M = B * T

# (contraction m, output N) of each MxV the population forward sends
# through the bank kernels: L0 and L1..L3 per direction (3 gates x hidden),
# the projections, the FC head
_BI = 2 * PAPER_CFG.hidden
LAYERS = {
    "L0": (PAPER_CFG.input_dim, 3 * PAPER_CFG.hidden),
    "Pr": (_BI, PAPER_CFG.proj),
    "L1": (PAPER_CFG.proj, 3 * PAPER_CFG.hidden),
    "FC": (_BI, PAPER_CFG.n_outputs),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """ShapeDtypeStruct factory on one described chip. The persistent
    compile cache is off meanwhile: a compile for a described chip can be
    written to it but not read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compiled_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("layer", sorted(LAYERS))
def test_bank_mxv_pop(spec, layer):
    m, n = LAYERS[layer]
    _compiled_kernel(ops.bank_mxv_pop.lower(
        spec((P, M, m)), spec((4, m, n)), spec((P,), jnp.int32),
        interpret=False).compile())


@pytest.mark.parametrize("layer", sorted(LAYERS))
def test_bank_qmm_pop_packed(spec, layer):
    m, n = LAYERS[layer]
    packed = {"q2": spec((-(-m // 4), n), jnp.int8),
              "q4": spec((-(-m // 2), n), jnp.int8),
              "q8": spec((m, n), jnp.int8),
              "q16": spec((m, n), jnp.int16),
              "scale": spec((4, 1))}
    _compiled_kernel(ops.bank_qmm_pop.lower(
        spec((P, M, m)), packed, spec((P,), jnp.int32),
        interpret=False).compile())


def test_sru_scan_pop(spec):
    n = PAPER_CFG.hidden
    _compiled_kernel(ops.sru_scan_pop.lower(
        *[spec((P, B, T, n))] * 3, *[spec((n,))] * 4,
        interpret=False).compile())


@pytest.mark.parametrize("bits", [8, 4, 2])
def test_quant_matmul(spec, bits):
    k, n = LAYERS["FC"]
    _compiled_kernel(ops.quant_matmul.lower(
        spec((M, k)), spec((k * bits // 8, n), jnp.int8), spec((n,)),
        bits=bits, interpret=False).compile())
