"""Packing / wire-format tests: roundtrip properties, manifest integrity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core import packing

# -- strategies --------------------------------------------------------------

_dtypes = st.sampled_from([jnp.float32, jnp.bfloat16, jnp.float16, jnp.int32])
_shapes = st.lists(st.integers(1, 5), min_size=0, max_size=3).map(tuple)


@st.composite
def pytrees(draw):
    n = draw(st.integers(1, 5))
    tree = {}
    for i in range(n):
        shape = draw(_shapes)
        dtype = draw(_dtypes)
        size = int(np.prod(shape)) if shape else 1
        vals = draw(
            st.lists(
                st.floats(-100, 100, allow_nan=False, width=16),
                min_size=size, max_size=size,
            )
        )
        arr = jnp.asarray(np.array(vals, np.float32).reshape(shape)).astype(dtype)
        tree[f"leaf_{i}"] = arr
    return tree


# -- properties --------------------------------------------------------------


@settings(max_examples=30, deadline=None)
@given(pytrees())
def test_numeric_roundtrip(tree):
    m = packing.build_manifest(tree)
    buf = packing.pack_numeric(tree)
    assert buf.shape == (m.total_elements,)
    back = packing.unpack_numeric(buf, m)
    for a, b in zip(jax.tree_util.tree_leaves(tree), jax.tree_util.tree_leaves(back)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), rtol=1e-2, atol=1e-2
        )


@settings(max_examples=30, deadline=None)
@given(pytrees())
def test_bytes_roundtrip_bitexact(tree):
    buf, m = packing.pack_bytes(tree)
    assert buf.dtype == np.uint8 and buf.shape == (m.total_bytes,)
    back = packing.unpack_bytes(buf, m)
    for a, b in zip(jax.tree_util.tree_leaves(tree), jax.tree_util.tree_leaves(back)):
        assert a.dtype == b.dtype
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def _per_leaf_unpack(buffer, manifest):
    """The per-leaf decode ``unpack_bytes`` replaces: one transfer per leaf."""
    leaves = []
    cursor = 0
    for spec in manifest.specs:
        if spec.dtype == "bool":
            leaf = packing.wire_view(buffer, cursor, spec.size, np.uint8).astype(bool)
        else:
            leaf = packing.wire_view(buffer, cursor, spec.size, spec.dtype)
        leaves.append(jax.device_put(leaf.reshape(spec.shape)))
        cursor += spec.nbytes
    return jax.tree_util.tree_unflatten(manifest.treedef, leaves)


def _bits(shape, dtype, seed):
    """Random bit patterns of ``dtype``: NaN payloads, -0.0 and subnormals too."""
    dt = np.dtype(jnp.dtype(dtype))
    size = int(np.prod(shape))
    raw = np.random.default_rng(seed).integers(0, 256, size * dt.itemsize, np.uint8)
    if dt == np.bool_:
        return (raw % 2).astype(bool).reshape(shape)
    return raw.view(dt).reshape(shape)


_UNPACK_TREES = {
    "f32-200-leaves": lambda: {
        f"layer_{i:03d}": _bits((i % 7 + 1, 3 + i % 5), np.float32, i) for i in range(200)
    },
    "alternating-dtypes": lambda: [
        _bits(shape, dtype, i)
        for i, (shape, dtype) in enumerate(
            [((4, 3), np.float32), ((5,), jnp.bfloat16), ((7,), np.int8), ((3,), bool)] * 3
        )
    ],
    "scalar-and-zero-size": lambda: {
        "a": _bits((), np.float32, 1), "b": np.zeros((0, 3), np.float32),
        "c": _bits((), np.int32, 2), "d": np.zeros((0,), bool),
        "e": _bits((2, 0, 4), jnp.bfloat16, 3), "f": _bits((6,), np.float32, 4),
    },
    "single-leaf": lambda: _bits((33, 17), np.float32, 5),
}


@pytest.mark.parametrize("name", sorted(_UNPACK_TREES))
def test_unpack_bytes_matches_per_leaf_decode(name):
    """One transfer per dtype run and one split program decode the same
    tree, bit for bit, as one transfer per leaf."""
    tree = _UNPACK_TREES[name]()
    buf, m = packing.pack_bytes(tree)
    got, want = packing.unpack_bytes(buf, m), _per_leaf_unpack(buf, m)
    assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(tree)
    for a, b, spec in zip(jax.tree_util.tree_leaves(got),
                          jax.tree_util.tree_leaves(want), m.specs):
        assert isinstance(a, jax.Array)
        assert a.dtype == b.dtype == jnp.dtype(spec.dtype)
        assert a.shape == b.shape == spec.shape
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    runs = packing.wire_runs(m)
    assert sum(len(r.shapes) for r in runs) == len(m.specs)
    assert all(a.dtype != b.dtype for a, b in zip(runs, runs[1:]))


@settings(max_examples=30, deadline=None)
@given(pytrees())
def test_pack_bytes_from_numeric_matches_pytree_pack(tree):
    """The broadcast fast path (wire bytes straight off the flat numeric
    buffer) must serialize exactly what the numeric state decodes to."""
    m = packing.build_manifest(tree)
    num = packing.pack_numeric(tree)
    want, _ = packing.pack_bytes(packing.unpack_numeric(num, m))
    got = packing.pack_bytes_from_numeric(num, m)
    assert got.dtype == np.uint8
    assert want.tobytes() == got.tobytes()
    # zero-padded tails (arena row alignment) never reach the wire
    padded = packing.pack_numeric(tree, pad_to=128)
    assert packing.pack_bytes_from_numeric(padded, m).tobytes() == want.tobytes()


def test_manifest_offsets_contiguous():
    tree = {"a": jnp.zeros((3, 4)), "b": jnp.zeros((5,), jnp.bfloat16), "c": jnp.zeros(())}
    m = packing.build_manifest(tree)
    offset = 0
    for spec in m.specs:
        assert spec.offset == offset
        offset += spec.size
    assert m.total_elements == offset == 3 * 4 + 5 + 1


def test_pack_numeric_jit_compatible():
    tree = {"w": jnp.ones((4, 4)), "b": jnp.zeros((4,))}
    out = jax.jit(packing.pack_numeric)(tree)
    assert out.shape == (20,)


def test_num_params():
    tree = {"w": jnp.ones((4, 4)), "b": jnp.zeros((4,)), "s": jnp.zeros(())}
    assert packing.num_params(tree) == 21


def test_unpack_restores_structure():
    tree = {"outer": {"inner": [jnp.ones((2,)), jnp.zeros((3,))]}}
    m = packing.build_manifest(tree)
    back = packing.unpack_numeric(packing.pack_numeric(tree), m)
    assert jax.tree_util.tree_structure(back) == jax.tree_util.tree_structure(tree)
