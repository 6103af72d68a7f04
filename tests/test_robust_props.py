"""Property tests for the robust (order-statistic) aggregation rules.

The byzantine-robust controller stands on four algebraic guarantees of
``coordinate_median`` / ``trimmed_mean`` and their masked arena forms
(``core/aggregation.py``):

* **mask/dense agreement** — a masked rule over a fully-valid arena equals
  the dense rule over the same rows stacked (no re-stack needed, ever);
* **row-permutation invariance** — order statistics cannot depend on
  arrival order (the arena writes rows in registration order; a shuffled
  cohort must aggregate identically);
* **boundedness** — a trimmed mean lies inside the per-coordinate
  [min, max] envelope of the valid rows (an adversary cannot drag the
  global model outside what *some* learner proposed);
* **minority resistance** — with fewer than half the rows corrupted
  arbitrarily, the coordinate median stays inside the honest rows'
  envelope, and a trimmed mean with ``trim_k`` at least the corruption
  count does too.

Runs under the real `hypothesis` when installed, else the deterministic
``hypothesis_compat`` fallback engine.
"""

import jax.numpy as jnp
import numpy as np

from hypothesis_compat import given, settings, st
from repro.core import aggregation


@st.composite
def _arenas(draw, min_rows=1, max_rows=7):
    """A small (n, p) float matrix with per-row weights, as nested lists."""
    n = draw(st.integers(min_value=min_rows, max_value=max_rows))
    p = draw(st.integers(min_value=1, max_value=9))
    rows = [
        [draw(st.floats(min_value=-100.0, max_value=100.0)) for _ in range(p)]
        for _ in range(n)
    ]
    weights = [draw(st.floats(min_value=0.5, max_value=10.0)) for _ in range(n)]
    return rows, weights


def _as_arrays(rows, weights):
    arena = jnp.asarray(np.asarray(rows, np.float32))
    w = jnp.asarray(np.asarray(weights, np.float32))
    mask = jnp.ones((arena.shape[0],), jnp.float32)
    return arena, w, mask


@settings(max_examples=40, deadline=None)
@given(data=_arenas())
def test_masked_median_equals_dense_under_full_mask(data):
    rows, weights = data
    arena, w, mask = _as_arrays(rows, weights)
    masked = np.asarray(aggregation.masked_coordinate_median(arena, w, mask))
    dense = np.asarray(aggregation.coordinate_median(arena))
    np.testing.assert_allclose(masked, dense, rtol=1e-6, atol=1e-6)


@settings(max_examples=40, deadline=None)
@given(data=_arenas(min_rows=3))
def test_masked_trimmed_mean_equals_dense_under_full_mask(data):
    rows, weights = data
    arena, w, mask = _as_arrays(rows, weights)
    masked = np.asarray(aggregation.masked_trimmed_mean(arena, w, mask, 1))
    dense = np.asarray(aggregation.trimmed_mean(arena, 1))
    np.testing.assert_allclose(masked, dense, rtol=1e-5, atol=1e-5)


@settings(max_examples=40, deadline=None)
@given(data=_arenas(min_rows=3), seed=st.integers(min_value=0, max_value=999))
def test_row_permutation_invariance(data, seed):
    rows, weights = data
    arena, w, mask = _as_arrays(rows, weights)
    perm = np.random.default_rng(seed).permutation(arena.shape[0])
    arena_p, w_p, mask_p = arena[perm], w[perm], mask[perm]
    for fn in (
        lambda a, ww, m: aggregation.masked_coordinate_median(a, ww, m),
        lambda a, ww, m: aggregation.masked_trimmed_mean(a, ww, m, 1),
    ):
        np.testing.assert_allclose(
            np.asarray(fn(arena, w, mask)),
            np.asarray(fn(arena_p, w_p, mask_p)),
            rtol=1e-6, atol=1e-6,
        )


@settings(max_examples=40, deadline=None)
@given(data=_arenas(min_rows=3))
def test_trimmed_mean_stays_inside_valid_envelope(data):
    rows, weights = data
    arena, w, mask = _as_arrays(rows, weights)
    out = np.asarray(aggregation.masked_trimmed_mean(arena, w, mask, 1))
    dense = np.asarray(arena)
    lo, hi = dense.min(axis=0), dense.max(axis=0)
    assert np.all(out >= lo - 1e-5) and np.all(out <= hi + 1e-5)


@settings(max_examples=40, deadline=None)
@given(
    data=_arenas(min_rows=3, max_rows=7),
    bad_value=st.floats(min_value=-1e6, max_value=1e6),
)
def test_median_resists_minority_corruption(data, bad_value):
    """Corrupt floor((n-1)/2) rows arbitrarily: the median of the full set
    stays inside the honest rows' per-coordinate envelope."""
    rows, weights = data
    honest = np.asarray(rows, np.float32)
    n = honest.shape[0]
    n_bad = (n - 1) // 2
    corrupt = np.full((n_bad, honest.shape[1]), np.float32(bad_value))
    arena = jnp.asarray(np.concatenate([honest, corrupt], axis=0))
    w = jnp.ones((n + n_bad,), jnp.float32)
    mask = jnp.ones((n + n_bad,), jnp.float32)
    med = np.asarray(aggregation.masked_coordinate_median(arena, w, mask))
    lo, hi = honest.min(axis=0), honest.max(axis=0)
    assert np.all(med >= lo - 1e-4) and np.all(med <= hi + 1e-4)


@settings(max_examples=25, deadline=None)
@given(
    data=_arenas(min_rows=3, max_rows=5),
    bad_value=st.floats(min_value=-1e6, max_value=1e6),
    n_bad=st.integers(min_value=1, max_value=2),
)
def test_trimmed_mean_discards_extremes_it_was_sized_for(data, bad_value, n_bad):
    """With trim_k >= the number of corrupted rows, the trimmed mean over
    honest+corrupt rows stays inside the honest envelope."""
    rows, weights = data
    honest = np.asarray(rows, np.float32)
    n = honest.shape[0]
    trim_k = n_bad
    if 2 * trim_k >= n + n_bad:
        return  # degenerate cohort: the rule falls back to the plain mean
    corrupt = np.full((n_bad, honest.shape[1]), np.float32(bad_value))
    arena = jnp.asarray(np.concatenate([honest, corrupt], axis=0))
    w = jnp.ones((n + n_bad,), jnp.float32)
    mask = jnp.ones((n + n_bad,), jnp.float32)
    out = np.asarray(aggregation.masked_trimmed_mean(arena, w, mask, trim_k))
    lo, hi = honest.min(axis=0), honest.max(axis=0)
    assert np.all(out >= lo - 1e-4) and np.all(out <= hi + 1e-4)


@settings(max_examples=40, deadline=None)
@given(data=_arenas(min_rows=4))
def test_invalid_rows_never_influence_the_reduce(data):
    """Garbage (NaN / 1e30) in masked-out rows must not leak: the masked
    rule over valid rows + garbage equals the dense rule over valid rows."""
    rows, weights = data
    valid = np.asarray(rows, np.float32)
    garbage = np.full((2, valid.shape[1]), np.nan, np.float32)
    garbage[1] = 1e30
    arena = jnp.asarray(np.concatenate([valid, garbage], axis=0))
    w = jnp.ones((arena.shape[0],), jnp.float32)
    mask = jnp.asarray(
        np.concatenate([np.ones(valid.shape[0]), np.zeros(2)]), jnp.float32
    )
    med = np.asarray(aggregation.masked_coordinate_median(arena, w, mask))
    np.testing.assert_allclose(
        med, np.asarray(aggregation.coordinate_median(jnp.asarray(valid))),
        rtol=1e-6, atol=1e-6,
    )
    if valid.shape[0] > 2:
        tm = np.asarray(aggregation.masked_trimmed_mean(arena, w, mask, 1))
        np.testing.assert_allclose(
            tm, np.asarray(aggregation.trimmed_mean(jnp.asarray(valid), 1)),
            rtol=1e-5, atol=1e-5,
        )
