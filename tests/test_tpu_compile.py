"""Compile the main path's Pallas kernels for a described TPU v5e.

No chip is attached: the TPU compiler that ships with the installed libtpu
compiles for a v5e that is only described (``jax.experimental.topologies``),
so a tiling or VMEM error the chip's compiler would raise fails here.  The
interpret-mode tests (``test_kernels.py``) check the numerics; these check
that the kernels lower.  Widths are the fedlm-100m arena row (P = 73,937,664
padded to 73,937,920) at N = 8 and N = 32, with block sizes from the
kernels' own choosers.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import fedavg, fused_agg, quantize, robust

P_ROW = 73_937_920  # fedlm-100m, padded to the arena's lane-aligned width
GROUP = quantize.DEFAULT_GROUP
f32, i8 = jnp.float32, jnp.int8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    # No fallback: a libtpu that cannot describe the chip fails these tests.
    return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A compile for a described device is written to the persistent cache
    # but cannot be read back without the chip; keep the cache off here.
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", old)


def _compile_for_chip(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo, "no Mosaic kernel in the compiled program"


@pytest.mark.parametrize("n", [8, 32])
def test_masked_fedavg_compiles_for_v5e(one_chip, n):
    bp = fedavg.choose_block_p_dividing(P_ROW, n)
    _compile_for_chip(
        lambda a, w, m: fedavg.masked_fedavg_pallas(a, w, m, block_p=bp, interpret=False),
        one_chip, ((n, P_ROW), f32), ((n,), f32), ((n,), f32),
    )


@pytest.mark.parametrize("n", [8, 32])
def test_masked_fedavg_q8_compiles_for_v5e(one_chip, n):
    bp = fused_agg.choose_block_p_q8_dividing(P_ROW, n, GROUP)
    _compile_for_chip(
        lambda q, s, w, m: fused_agg.masked_fedavg_q8_pallas(
            q, s, w, m, group=GROUP, block_p=bp, interpret=False),
        one_chip, ((n, P_ROW), i8), ((n, P_ROW // GROUP), f32), ((n,), f32), ((n,), f32),
    )


@pytest.mark.parametrize("n", [8, 32])
def test_masked_trimmed_mean_compiles_for_v5e(one_chip, n):
    bp = fedavg.choose_block_p_dividing(P_ROW, n, budget=robust.ROBUST_VMEM_BUDGET_BYTES)
    _compile_for_chip(
        lambda a, m: robust.masked_trimmed_mean_pallas(
            a, m, trim_k=1, block_p=bp, interpret=False),
        one_chip, ((n, P_ROW), f32), ((n,), f32),
    )


def test_quantize_compiles_for_v5e(one_chip):
    n_padded = quantize.wire_layout(P_ROW)[0]
    rows = quantize.effective_block_rows(P_ROW)
    _compile_for_chip(
        lambda x: quantize.quantize_pallas(x, GROUP, rows, interpret=False),
        one_chip, ((n_padded,), f32),
    )


def test_dequantize_compiles_for_v5e(one_chip):
    n_padded = quantize.wire_layout(P_ROW)[0]
    rows = quantize.effective_block_rows(P_ROW)
    _compile_for_chip(
        lambda q, s: quantize.dequantize_pallas(q, s, GROUP, rows, interpret=False),
        one_chip, ((n_padded,), i8), ((n_padded // GROUP,), f32),
    )
