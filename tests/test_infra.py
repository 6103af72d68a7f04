"""Data / optimizer / checkpoint / transport-codec substrate tests."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import latest_step, restore_checkpoint, save_checkpoint
from repro.data import dirichlet_partition, iid_partition, make_housing_data, make_lm_data
from repro.optim import adafactor, adam, adamw, apply_fedprox, momentum, sgd


# -- data --------------------------------------------------------------------


def test_iid_partition_disjoint_and_complete():
    shards = iid_partition(100, 7, seed=0)
    allidx = np.concatenate(shards)
    assert len(allidx) == 100 and len(np.unique(allidx)) == 100


def test_iid_partition_paper_mode():
    shards = iid_partition(506, 200, seed=0, per_learner=100, with_replacement=True)
    assert len(shards) == 200 and all(len(s) == 100 for s in shards)


def test_dirichlet_partition_skews():
    labels = np.repeat(np.arange(5), 200)
    even = dirichlet_partition(labels, 4, alpha=1000.0, seed=0)
    skew = dirichlet_partition(labels, 4, alpha=0.05, seed=0)

    def class_entropy(shards):
        ents = []
        for s in shards:
            if not len(s):
                continue
            c = np.bincount(labels[s], minlength=5) / len(s)
            c = c[c > 0]
            ents.append(-(c * np.log(c)).sum())
        return np.mean(ents)

    assert class_entropy(skew) < class_entropy(even)
    assert all(len(s) >= 1 for s in skew)


def test_lm_data_learnable_structure():
    toks = make_lm_data(16, 32, vocab_size=50, seed=0)
    assert toks.shape == (16, 33) and toks.max() < 50 and toks.min() >= 0
    # bigram copy structure exists: successor-of-previous appears often
    nxt = (toks[:, :-1] + 1) % 50
    frac = (toks[:, 1:] == nxt).mean()
    assert frac > 0.2


# -- optimizers ----------------------------------------------------------------


@pytest.mark.parametrize(
    "opt", [sgd(0.1), momentum(0.05), adam(0.05), adamw(0.05), adafactor(0.1)]
)
def test_optimizers_descend_quadratic(opt):
    params = {"w": jnp.full((6, 3), 2.0), "b": jnp.full((3,), -1.5)}
    loss = lambda p: jnp.sum(p["w"] ** 2) + jnp.sum(p["b"] ** 2)
    st = opt.init(params)
    l0 = float(loss(params))
    for _ in range(60):
        params, st = opt.apply(params, jax.grad(loss)(params), st)
    assert float(loss(params)) < 0.2 * l0, opt.name


def test_fedprox_pulls_towards_global():
    g = {"w": jnp.zeros((4,))}
    base = lambda p, b: jnp.sum((p["w"] - 10.0) ** 2)  # pulls towards 10
    prox = apply_fedprox(base, mu=100.0, global_params=g)  # dominates: stay near 0
    params = {"w": jnp.zeros((4,))}
    opt = sgd(0.005)
    st = opt.init(params)
    for _ in range(100):
        params, st = opt.apply(params, jax.grad(lambda p: prox(p, None))(params), st)
    assert float(jnp.max(params["w"])) < 1.0  # without prox it would go to ~10


# -- checkpoint ----------------------------------------------------------------


def test_checkpoint_roundtrip(tmp_path):
    d = str(tmp_path)
    params = {
        "w": jax.random.normal(jax.random.key(0), (8, 4), jnp.float32),
        "emb": jax.random.normal(jax.random.key(1), (10, 4), jnp.bfloat16),
    }
    save_checkpoint(d, 3, params, extra_arrays={"rounds": np.asarray([1, 2, 3])},
                    metadata={"arch": "test"})
    save_checkpoint(d, 7, params)
    assert latest_step(d) == 7
    back, extras, meta = restore_checkpoint(d, 3)
    assert meta["step"] == 3 and meta["arch"] == "test"
    np.testing.assert_array_equal(extras["rounds"], [1, 2, 3])
    for a, b in zip(jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(back)):
        assert a.dtype == b.dtype
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), rtol=1e-6
        )


def test_checkpoint_restore_latest(tmp_path):
    d = str(tmp_path)
    save_checkpoint(d, 1, {"w": jnp.ones((2,))})
    _, _, meta = restore_checkpoint(d)
    assert meta["step"] == 1


def test_checkpoint_missing_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        restore_checkpoint(str(tmp_path))


# -- entry-point plumbing ------------------------------------------------------

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_compile_cache_keeps_the_env_dir(monkeypatch, tmp_path):
    from repro.launch import compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    from repro.launch import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable_compile_cache()
        assert got == os.path.join(_REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_library_import_starts_no_backend_and_no_cache():
    import subprocess
    import sys

    code = (
        "import jax, repro.core, repro.kernels.ops, repro.launch.train\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n"
        "assert jax.config.jax_compilation_cache_dir is None\n"
    )
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(_REPO, "src")] + env.get("PYTHONPATH", "").split(os.pathsep)
    )
    subprocess.run([sys.executable, "-c", code], env=env, check=True, timeout=120)
