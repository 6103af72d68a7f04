"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles.

Kernels execute in interpret mode on CPU, which checks numerics only: it
does not apply the TPU's tiling or VMEM limits.  That the kernels lower for
the chip is checked by compiling them for a described v5e
(``tests/test_tpu_compile.py``), and on the chip by ``chip_smoke.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.fedavg import fedavg_pallas
from repro.kernels.quantize import dequantize_pallas, quantize_pallas


# ---------------------------------------------------------------------------
# fedavg kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [1, 2, 7, 64])
@pytest.mark.parametrize("p", [1024, 16384, 50_001])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fedavg_kernel_sweep(n, p, dtype):
    stack = (jax.random.normal(jax.random.key(n * p), (n, p)) * 3).astype(dtype)
    w = jax.random.uniform(jax.random.key(p), (n,)) + 0.05
    got = ops.fedavg(stack, w)
    want = ref.fedavg_ref(stack, w)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol, rtol=tol)


def test_fedavg_kernel_block_shapes():
    stack = jax.random.normal(jax.random.key(0), (4, 8192), jnp.float32)
    w = jnp.ones((4,))
    want = ref.fedavg_ref(stack, w)
    for block_p in (1024, 2048, 8192):
        got = fedavg_pallas(stack, w, block_p=block_p, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@settings(max_examples=15, deadline=None)
@given(n=st.integers(1, 12), seed=st.integers(0, 99))
def test_fedavg_kernel_property_matches_oracle(n, seed):
    p = 2048
    stack = jax.random.normal(jax.random.key(seed), (n, p), jnp.float32)
    w = jax.random.uniform(jax.random.key(seed + 1), (n,)) + 0.01
    np.testing.assert_allclose(
        np.asarray(ops.fedavg(stack, w)), np.asarray(ref.fedavg_ref(stack, w)),
        atol=1e-5,
    )


# ---------------------------------------------------------------------------
# robust (trimmed-mean) kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,trim_k", [(3, 1), (8, 1), (8, 3), (16, 4)])
@pytest.mark.parametrize("p", [1024, 5000])
def test_trimmed_mean_kernel_sweep(n, trim_k, p):
    arena = jax.random.normal(jax.random.key(n * p + trim_k), (n, p)) * 3
    mask = (jax.random.uniform(jax.random.key(p + n), (n,)) > 0.3).astype(
        jnp.float32
    )
    w = jnp.ones((n,))
    got = ops.masked_trimmed_mean(arena, w, mask, trim_k=trim_k)
    want = ref.masked_trimmed_mean_ref(arena, mask, trim_k)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_trimmed_mean_kernel_matches_core_rule():
    from repro.core import aggregation

    arena = jax.random.normal(jax.random.key(0), (12, 4096), jnp.float32)
    mask = jnp.ones((12,)).at[3].set(0.0).at[7].set(0.0)
    w = jnp.ones((12,))
    got = ops.masked_trimmed_mean(arena, w, mask, trim_k=2)
    want = aggregation.masked_trimmed_mean(arena, w, mask, 2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_trimmed_mean_kernel_ignores_dead_row_garbage():
    arena = np.ones((6, 2048), np.float32)
    arena[2] = np.nan
    arena[4] = 1e30
    mask = np.array([1, 1, 0, 1, 0, 1], np.float32)
    got = ops.masked_trimmed_mean(jnp.asarray(arena), jnp.ones((6,)),
                                  jnp.asarray(mask), trim_k=1)
    np.testing.assert_allclose(np.asarray(got), np.ones(2048), atol=1e-6)


def test_trimmed_mean_kernel_degenerate_cohort_falls_back():
    arena = jax.random.normal(jax.random.key(5), (8, 1024), jnp.float32)
    mask = jnp.zeros((8,)).at[0].set(1.0).at[5].set(1.0)
    got = ops.masked_trimmed_mean(arena, jnp.ones((8,)), mask, trim_k=2)
    want = (arena[0] + arena[5]) / 2.0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_trimmed_mean_kernel_trim_k_trace_error():
    arena = jnp.ones((4, 1024), jnp.float32)
    with pytest.raises(ValueError, match="trim_k"):
        ops.masked_trimmed_mean(arena, jnp.ones((4,)), jnp.ones((4,)),
                                trim_k=2, block_p=1024)


# ---------------------------------------------------------------------------
# quantize kernels
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("size", [16384, 65536, 100_000])
def test_quantize_kernel_matches_ref(size):
    x = jax.random.normal(jax.random.key(size), (size,), jnp.float32) * 5
    q, s = ops.quantize(x)
    pad = q.shape[0]
    qr, sr = ref.quantize_ref(jnp.pad(x, (0, pad - size)))
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)


def test_quantize_roundtrip_error_bound():
    x = jax.random.normal(jax.random.key(1), (32768,), jnp.float32) * 10
    q, s = ops.quantize(x)
    back = ops.dequantize(q, s, 32768)
    # per-group bound: |err| <= scale/2 = max|x|_group / 254
    xg = np.asarray(x).reshape(-1, 256)
    bound = np.abs(xg).max(1, keepdims=True) / 254.0 + 1e-7
    err = np.abs(np.asarray(back).reshape(-1, 256) - xg)
    assert (err <= bound).all()


def test_quantize_zero_block_safe():
    x = jnp.zeros((16384,), jnp.float32)
    q, s = ops.quantize(x)
    assert bool(jnp.all(q == 0))
    back = ops.dequantize(q, s, 16384)
    assert bool(jnp.all(back == 0))


def test_quant_codec_roundtrip_mixed_tree():
    tree = {
        "w": jax.random.normal(jax.random.key(0), (33, 57), jnp.bfloat16),
        "b": jax.random.normal(jax.random.key(1), (129,), jnp.float32),
        "step": jnp.asarray(7, jnp.int32),
    }
    dec = ops.QuantCodec.decode(ops.QuantCodec.encode(tree))
    assert dec["w"].shape == (33, 57) and dec["w"].dtype == jnp.bfloat16
    assert dec["b"].dtype == jnp.float32
    assert int(dec["step"]) == 7
    rel = np.abs(np.asarray(dec["b"]) - np.asarray(tree["b"]))
    assert rel.max() < np.abs(np.asarray(tree["b"])).max() / 100


def test_choose_block_p_fits_vmem():
    from repro.kernels.fedavg import VMEM_BUDGET_BYTES, choose_block_p

    for n in (2, 8, 50, 200, 1000):
        bp = choose_block_p(n)
        working = 2 * n * bp * 4 + bp * 4 + n * 4
        assert working <= VMEM_BUDGET_BYTES, (n, bp, working)
        assert bp % 1024 == 0 or bp == 1024
        got = ops.fedavg(
            jax.random.normal(jax.random.key(n), (n, 4096), jnp.float32),
            jnp.ones((n,)),
        )
        want = ref.fedavg_ref(
            jax.random.normal(jax.random.key(n), (n, 4096), jnp.float32),
            jnp.ones((n,)),
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


# ---------------------------------------------------------------------------
# fused dequant-into-aggregate kernel
# ---------------------------------------------------------------------------


def _quantized_arena(n, p, seed=0, group=256, scale_spread=5.0):
    """A synthetic quantized arena: random int8 groups + spread-out scales."""
    rng = np.random.default_rng(seed)
    q = rng.integers(-127, 128, size=(n, p), dtype=np.int8)
    s = rng.uniform(0.01, scale_spread, size=(n, p // group)).astype(np.float32)
    return jnp.asarray(q), jnp.asarray(s)


@pytest.mark.parametrize("n,p", [(3, 4096), (8, 16384), (33, 8192)])
def test_fused_q8_kernel_matches_oracle(n, p):
    q, s = _quantized_arena(n, p, seed=n)
    w = jnp.asarray(np.random.default_rng(n + 1).uniform(1, 50, n), jnp.float32)
    mask = jnp.asarray((np.arange(n) % 3 != 1).astype(np.float32))
    got = ops.masked_fedavg_q8(q, s, w, mask)
    want = ref.masked_fedavg_q8_ref(q, s, w, mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_fused_q8_kernel_block_sweep():
    q, s = _quantized_arena(5, 8192, seed=7)
    w = jnp.ones((5,), jnp.float32)
    mask = jnp.asarray([1, 0, 1, 1, 0], jnp.float32)
    want = np.asarray(ref.masked_fedavg_q8_ref(q, s, w, mask))
    for block_p in (1024, 2048, 4096, 8192):
        got = ops.masked_fedavg_q8(q, s, w, mask, block_p=block_p)
        np.testing.assert_allclose(np.asarray(got), want,
                                   rtol=2e-5, atol=2e-5, err_msg=str(block_p))


def test_fused_q8_kernel_nondefault_group():
    q, s = _quantized_arena(4, 4096, seed=3, group=512)
    w = jnp.asarray([1.0, 2.0, 3.0, 4.0])
    mask = jnp.ones((4,), jnp.float32)
    got = ops.masked_fedavg_q8(q, s, w, mask, group=512)
    want = ref.masked_fedavg_q8_ref(q, s, w, mask, group=512)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_fused_q8_kernel_all_invalid_mask_is_zero():
    q, s = _quantized_arena(4, 2048, seed=9)
    out = ops.masked_fedavg_q8(q, s, jnp.ones((4,)), jnp.zeros((4,)))
    assert bool(jnp.all(out == 0.0))


def test_fused_q8_kernel_dead_row_garbage_ignored():
    q, s = _quantized_arena(4, 2048, seed=11)
    # poison a masked-out row with extreme values and scales
    q = q.at[2].set(127)
    s = s.at[2].set(1e30)
    mask = jnp.asarray([1, 1, 0, 1], jnp.float32)
    got = ops.masked_fedavg_q8(q, s, jnp.ones((4,)), mask)
    want = ref.masked_fedavg_q8_ref(q, s, jnp.ones((4,)), mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    assert bool(jnp.all(jnp.isfinite(got)))


def test_fused_q8_kernel_shape_errors():
    from repro.kernels.fused_agg import masked_fedavg_q8_pallas

    q, s = _quantized_arena(3, 2048, seed=1)
    w, m = jnp.ones((3,)), jnp.ones((3,))
    with pytest.raises(ValueError, match="block_p"):
        masked_fedavg_q8_pallas(q, s, w, m, block_p=1536, interpret=True)
    with pytest.raises(ValueError, match="scales"):
        masked_fedavg_q8_pallas(q, s[:, :-1], w, m, block_p=2048,
                                interpret=True)


def test_fused_q8_kernel_pads_non_aligned_width():
    # 2048 + one group: not a multiple of any legal block — ops must pad.
    q, s = _quantized_arena(3, 2048 + 256, seed=5)
    got = ops.masked_fedavg_q8(q, s, jnp.ones((3,)), jnp.ones((3,)),
                               block_p=1024)
    want = ref.masked_fedavg_q8_ref(q, s, jnp.ones((3,)), jnp.ones((3,)))
    assert got.shape == (2048 + 256,)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_choose_block_p_q8_fits_vmem_and_divides():
    from repro.kernels.fused_agg import (
        VMEM_BUDGET_BYTES, choose_block_p_q8, choose_block_p_q8_dividing,
    )

    for n in (2, 8, 50, 200, 1000):
        bp = choose_block_p_q8(n)
        # int8 values + f32 out-tile accum + scales + weights/mask vectors
        working = n * bp + 4 * n * bp + 4 * n * (bp // 256) + 4 * bp + 8 * n
        assert working <= VMEM_BUDGET_BYTES, (n, bp, working)
        assert bp % 1024 == 0
    bp = choose_block_p_q8_dividing(16 * 1024, 8, 256)
    assert (16 * 1024) % bp == 0


def test_dequantize_scale_count_error():
    q = jnp.zeros((16384,), jnp.int8)
    s = jnp.zeros((3,), jnp.float32)  # wrong: needs 64 scales
    with pytest.raises(ValueError, match="scales"):
        ops.dequantize(q, s, 16384)
