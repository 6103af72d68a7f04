"""Serving launcher: prefill a batch of requests, then decode tokens.

Runs reduced configs on the host (the full-scale serve steps are lowered by
``launch/dryrun.py``).  Exercises the exact same ``make_serve_step`` that the
dry-run proves on the production mesh.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-4b \
        --batch 4 --prompt-len 32 --gen-len 16

``--push-replicas N`` additionally simulates publishing the served weights to
N replica hosts through the federation transport's serialize-once broadcast
(the same ``Channel.broadcast`` the controller's dispatch uses), printing the
measured one-serialization fan-out accounting.  ``--replica-upload raw|int8``
then echoes the weights back per replica through the measured uplink half
(``Channel.upload``) so both wire directions are accounted.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCHITECTURES, get_reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import make_serve_step
from repro.models import kvcache, transformer


def push_to_replicas(
    params,
    n_replicas: int,
    bandwidth_gbps: float = 10.0,
    replica_upload: str | None = None,
) -> None:
    """Publish model weights to ``n_replicas`` serving hosts, serialize-once.

    One ``Channel.broadcast`` serialization, N shared envelopes; each replica
    deserializes its own copy (one batched device_put of the whole tree).
    Prints bytes-on-wire and the broadcast-vs-per-send serialization ratio.

    ``replica_upload`` additionally exercises the measured uplink: every
    replica reports its resident weights back through ``Channel.upload``
    (health-check echo) with the given codec (``"raw"`` or ``"int8"``), so
    the printed accounting covers both wire directions — the full-duplex
    contract the federation controller runs on.
    """
    from repro.core import Channel, packing

    ch = Channel(bandwidth_gbps=bandwidth_gbps, upload_codec=replica_upload or "raw")
    t0 = time.time()
    broadcast = ch.broadcast(params=params)
    envelopes = [broadcast.to({"replica": i}) for i in range(n_replicas)]
    replica_params = ch.recv(envelopes[0])  # one replica decodes as a check
    jax.block_until_ready(replica_params)
    elapsed = time.time() - t0
    tm = ch.telemetry  # the unified observability surface (docs/OBSERVABILITY.md)
    print(
        f"push: {n_replicas} replicas, "
        f"{tm.value('channel.bytes_moved')/1e6:.1f}MB on wire, "
        f"{tm.value('channel.serializations')} serialization(s) "
        f"(vs {n_replicas} per-send), "
        f"{elapsed:.3f}s incl. one decode, "
        f"virtual wire {tm.value('channel.virtual_wire_s', 0.0)*1e3:.1f}ms"
    )
    assert tm.value("channel.serializations") == 1
    assert tm.value("channel.messages") == n_replicas
    if replica_upload:
        buf = packing.pack_numeric(replica_params)
        jax.block_until_ready(buf)
        t0 = time.time()
        for i in range(n_replicas):
            env = ch.upload(buf, metadata={"replica": i})
        echo = ch.recv_upload(env)  # the server decodes one echo as a check
        jax.block_until_ready(echo)
        elapsed = time.time() - t0
        down = tm.value("channel.bytes_moved")
        up = tm.value("channel.upload_bytes")
        print(
            f"echo: {n_replicas} uploads ({replica_upload}), "
            f"{up/1e6:.1f}MB on wire "
            f"({down / max(up, 1):.2f}x vs downlink), "
            f"{elapsed:.3f}s incl. one decode, "
            f"virtual wire {tm.value('channel.upload_virtual_wire_s', 0.0)*1e3:.1f}ms"
        )
        assert tm.value("channel.upload_messages") == n_replicas
        # per-replica round-trip estimate — the same bandwidth-model API the
        # federation's wire-cost-aware task sizing consumes
        rt = ch.round_trip_s(down // n_replicas, up // n_replicas)
        print(f"modeled per-replica round-trip: {rt*1e3:.1f}ms "
              f"(push down + {replica_upload} echo up)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-4b", choices=ARCHITECTURES)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--push-replicas", type=int, default=0,
                    help="simulate serialize-once weight push to N replicas")
    ap.add_argument("--replica-upload", choices=("raw", "int8"), default=None,
                    help="also echo weights back per replica through the "
                         "measured uplink with this codec")
    args = ap.parse_args()
    enable_compile_cache()
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")

    cfg = get_reduced(args.arch)
    params = transformer.init_params(jax.random.key(args.seed), cfg)
    if args.push_replicas:
        push_to_replicas(params, args.push_replicas,
                         replica_upload=args.replica_upload)
    B = args.batch
    max_len = args.prompt_len + args.gen_len

    prompts = jax.random.randint(
        jax.random.key(args.seed + 1), (B, args.prompt_len), 0, cfg.vocab_size
    )
    memory = None
    if cfg.is_encoder_decoder:
        frames = jax.random.normal(
            jax.random.key(2), (B, cfg.encoder_seq_len, cfg.frontend_dim), jnp.float32
        )
        memory = transformer.encode(params, frames, cfg)

    serve_step = jax.jit(make_serve_step(cfg), static_argnames=())

    # prefill by stepping the decoder over the prompt (cache-building path);
    # production prefill uses the fused forward (see dryrun prefill shapes).
    caches = kvcache.init_cache(cfg, B, max_len)
    t0 = time.time()
    tok = prompts[:, :1]
    for t in range(args.prompt_len):
        nxt, caches = serve_step(params, caches, prompts[:, t : t + 1],
                                 jnp.asarray(t, jnp.int32), memory)
    prefill_s = time.time() - t0

    generated = []
    t0 = time.time()
    for t in range(args.prompt_len, max_len):
        nxt, caches = serve_step(params, caches, nxt, jnp.asarray(t, jnp.int32), memory)
        generated.append(nxt)
    jax.block_until_ready(nxt)
    decode_s = time.time() - t0

    out = jnp.concatenate(generated, axis=1)
    print(f"arch={cfg.name} batch={B}")
    print(f"prefill: {args.prompt_len} steps in {prefill_s:.2f}s")
    print(
        f"decode:  {args.gen_len} tokens in {decode_s:.2f}s "
        f"({B * args.gen_len / decode_s:.1f} tok/s batch-aggregate)"
    )
    print("sample token ids:", out[0, :12].tolist())
    assert not bool(jnp.any(out < 0)) and not bool(jnp.any(out >= cfg.padded_vocab_size))


if __name__ == "__main__":
    main()
