"""Multi-device tests: run in a SUBPROCESS with 8 forced host devices so the
main test process keeps 1 device (smoke tests must not see 512).

Covers: sharded zero-collective aggregation, hierarchical pod-axis FedAvg,
expert-parallel MoE on a real (2,2) mesh, and a reduced train_step under pjit
on a (2,2,2) pod mesh — the same code paths the production dry-run lowers.
"""

import os
import subprocess
import sys
import textwrap

import pytest

# Every test here spawns a subprocess with XLA-forced host devices; the CI
# tier-1 lane runs them (8 forced devices) to exercise real mesh sharding.
pytestmark = pytest.mark.multidevice

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(script)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_fedavg_sharded_no_collectives():
    _run("""
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_auto_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.aggregation import weighted_average
        mesh = make_auto_mesh((4, 2), ("data", "model"))
        stack = jax.random.normal(jax.random.key(0), (5, 4096), jnp.float32)
        w = jnp.arange(1., 6.)
        fn = jax.jit(
            weighted_average,
            in_shardings=(NamedSharding(mesh, P(None, ("data","model"))),
                          NamedSharding(mesh, P())),
            out_shardings=NamedSharding(mesh, P(("data","model"))),
        )
        with mesh:
            lowered = fn.lower(stack, w)
            compiled = lowered.compile()
            hlo = compiled.as_text()
            for op in ("all-reduce", "all-gather", "all-to-all", "collective-permute"):
                assert f" {op}(" not in hlo, f"unexpected collective {op}"
            got = fn(stack, w)
        want = weighted_average(stack, w)
        assert float(jnp.max(jnp.abs(got - want))) < 1e-5
        print("NO-COLLECTIVE AGG OK")
    """)


def test_hierarchical_pod_fedavg():
    _run("""
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_auto_mesh
        from repro.core.aggregation import hierarchical_fedavg, weighted_average
        mesh = make_auto_mesh((2, 2, 2), ("pod", "data", "model"))
        n_pods, P_ = 2, 1024
        stack = jax.random.normal(jax.random.key(0), (n_pods, P_), jnp.float32)
        w = jnp.asarray([1.0, 3.0])
        with mesh:
            agg = jax.jit(hierarchical_fedavg(mesh))(stack, w)
        want = weighted_average(stack, w)
        err = float(jnp.max(jnp.abs(agg - want)))
        assert err < 1e-5, err
        print("HIERARCHICAL AGG OK")
    """)


def test_moe_ep_on_2x2_mesh_matches_dense():
    _run("""
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_auto_mesh
        from repro.models.config import ModelConfig
        from repro.models import layers
        from repro.models.sharding import make_policy
        cfg = ModelConfig(name='t', arch_type='moe', n_layers=1, d_model=32,
                          n_heads=4, n_kv_heads=4, d_ff=64, vocab_size=100,
                          n_experts=4, top_k=2, moe_d_ff=48, n_shared_experts=1,
                          shared_d_ff=48, capacity_factor=4.0)
        p = layers.init_moe(jax.random.key(0), cfg)
        x = jax.random.normal(jax.random.key(1), (4, 8, 32), jnp.float32)
        y_dense, _ = layers.apply_moe_dense(p, x, cfg)
        mesh = make_auto_mesh((2, 2), ("data", "model"))
        pol = make_policy(cfg, mesh)
        with mesh:
            y_ep, _ = jax.jit(lambda pp, xx: layers.apply_moe_ep(pp, xx, cfg, pol))(p, x)
        err = float(jnp.max(jnp.abs(y_dense - y_ep)))
        assert err < 1e-4, err
        print("MOE EP 2x2 OK")
    """)


def test_reduced_train_step_on_pod_mesh():
    _run("""
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_auto_mesh
        from repro.configs import get_reduced
        from repro.launch.specs import input_specs
        from repro.launch.steps import make_train_step
        from repro.models import transformer
        from repro.models.sharding import make_policy
        from repro.optim import sgd
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = make_auto_mesh((2, 2, 2), ("pod", "data", "model"))
        cfg = get_reduced("qwen3-14b")
        pol = make_policy(cfg, mesh, multi_pod=True, fsdp=True)
        params = transformer.init_params(jax.random.key(0), cfg)
        opt = sgd(0.1)
        B, S = 4, 16
        tokens = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
        batch = {"tokens": tokens, "labels": tokens}
        step = make_train_step(cfg, opt, pol)
        with mesh:
            newp, _, loss = jax.jit(step)(params, opt.init(params), batch)
        assert bool(jnp.isfinite(loss)), float(loss)
        # distributed result must match single-device execution
        step1 = make_train_step(cfg, opt, None)
        newp1, _, loss1 = jax.jit(step1)(params, opt.init(params), batch)
        assert abs(float(loss) - float(loss1)) < 1e-3, (float(loss), float(loss1))
        print("POD-MESH TRAIN STEP OK", float(loss))
    """)


def test_serve_step_with_sharded_cache():
    _run("""
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_auto_mesh
        from repro.configs import get_reduced
        from repro.launch.steps import make_serve_step
        from repro.models import kvcache, transformer
        from repro.models.sharding import make_policy

        mesh = make_auto_mesh((2, 2), ("data", "model"))
        cfg = get_reduced("gemma3-4b")
        pol = make_policy(cfg, mesh)
        params = transformer.init_params(jax.random.key(0), cfg)
        B = 4
        caches = kvcache.init_cache(cfg, B, 32)
        tok = jnp.zeros((B, 1), jnp.int32)
        step = make_serve_step(cfg, pol)
        with mesh:
            nxt, caches = jax.jit(step)(params, caches, tok, jnp.asarray(0, jnp.int32), None)
        assert nxt.shape == (B, 1)
        assert int(nxt.max()) < cfg.padded_vocab_size
        print("SHARDED SERVE OK")
    """)


@pytest.mark.slow
def test_flash_decode_matches_unsharded():
    """shard_map flash-decoding (seq-sharded cache) must equal the plain
    decode path — GQA + sliding + MLA, on a real (2,2) mesh."""
    _run("""
        import dataclasses, jax, jax.numpy as jnp
        from repro.launch.mesh import make_auto_mesh
        from repro.configs import get_reduced
        from repro.models import kvcache, transformer
        from repro.models.sharding import make_policy

        mesh = make_auto_mesh((2, 2), ("data", "model"))
        for arch in ("gemma3-4b", "deepseek-v3-671b", "qwen3-14b"):
            cfg = dataclasses.replace(get_reduced(arch), dtype=jnp.float32)
            pol = make_policy(cfg, mesh)
            params = transformer.init_params(jax.random.key(0), cfg)
            B, S = 4, 8
            toks = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
            # unsharded reference
            cache_r = kvcache.init_cache(cfg, B, 16, dtype=jnp.float32)
            outs_r = []
            for t in range(S):
                lg, cache_r = transformer.decode_step(
                    params, toks[:, t:t+1], cache_r, jnp.asarray(t, jnp.int32), cfg)
                outs_r.append(lg)
            ref = jnp.concatenate(outs_r, 1)
            # sharded flash decode
            cache_s = kvcache.init_cache(cfg, B, 16, dtype=jnp.float32)
            outs_s = []
            with mesh:
                step = jax.jit(lambda p, c, t, i: transformer.decode_step(
                    p, t, c, i, cfg, policy=pol))
                for t in range(S):
                    lg, cache_s = step(params, cache_s, toks[:, t:t+1],
                                       jnp.asarray(t, jnp.int32))
                    outs_s.append(lg)
            got = jnp.concatenate(outs_s, 1)
            err = float(jnp.max(jnp.abs(got - ref)))
            assert err < 2e-3, (arch, err)
            print(arch, "flash-decode err", err)
        print("FLASH DECODE OK")
    """)


def test_moe_2d_decode_matches_unsharded():
    """Weights-stationary 2D expert-parallel decode (serving layout) must
    match the single-device decode output."""
    _run("""
        import dataclasses, jax, jax.numpy as jnp
        from repro.launch.mesh import make_auto_mesh
        from repro.configs import get_reduced
        from repro.models import kvcache, transformer
        from repro.models.sharding import make_policy

        mesh = make_auto_mesh((2, 2), ("data", "model"))
        cfg = dataclasses.replace(get_reduced("deepseek-v3-671b"), dtype=jnp.float32,
                                  mtp_depth=0)
        pol = make_policy(cfg, mesh, fsdp=True, serving=True)
        params = transformer.init_params(jax.random.key(0), cfg)
        B, S = 4, 6
        toks = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
        cache_r = kvcache.init_cache(cfg, B, 8, dtype=jnp.float32)
        outs_r = []
        for t in range(S):
            lg, cache_r = transformer.decode_step(
                params, toks[:, t:t+1], cache_r, jnp.asarray(t, jnp.int32), cfg)
            outs_r.append(lg)
        ref = jnp.concatenate(outs_r, 1)
        cache_s = kvcache.init_cache(cfg, B, 8, dtype=jnp.float32)
        outs_s = []
        with mesh:
            step = jax.jit(lambda p, c, t, i: transformer.decode_step(
                p, t, c, i, cfg, policy=pol))
            for t in range(S):
                lg, cache_s = step(params, cache_s, toks[:, t:t+1],
                                   jnp.asarray(t, jnp.int32))
                outs_s.append(lg)
        got = jnp.concatenate(outs_s, 1)
        err = float(jnp.max(jnp.abs(got - ref)))
        assert err < 5e-3, err
        print("2D-EP DECODE OK", err)
    """)
