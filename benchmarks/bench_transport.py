"""Transport / serialization benchmark (the dispatch-time share of
Figs. 5a/5d...): per-tensor pickle (naive) vs flat-byte packing (paper's
proto-tensor) vs flat packing + int8 Pallas codec (beyond paper), plus the
serialize-once broadcast fan-out vs legacy per-send dispatch, plus the
measured **uplink** (``--upload``): raw vs int8 vs top-k sparse codecs over the
``Channel.upload``/``recv_upload`` half — the dominant wire direction of a
federation round (N uploads vs 1 broadcast).

Reports bytes-on-wire and serialize+deserialize wall time per model size.
"""

from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.timing import bench
from repro.configs import housing_mlp
from repro.core import Channel, naive, packing
from repro.core.transport import TopkUploadCodec
from repro.kernels.ops import QuantCodec
from repro.launch.compile_cache import enable_compile_cache
from repro.models import mlp as mlp_model


def run(sizes=("100k", "1m", "10m")):
    rows = []
    for size in sizes:
        cfg = housing_mlp.config(size)
        params = mlp_model.init_params(jax.random.key(0), cfg)
        treedef = jax.tree_util.tree_structure(params)

        def naive_rt():
            blobs = naive.naive_serialize(params)
            naive.naive_deserialize(blobs, treedef)
            return sum(len(b) for b in blobs)

        def packed_rt():
            buf, m = packing.pack_bytes(params)
            packing.unpack_bytes(buf, m)
            return buf.nbytes

        codec = QuantCodec()

        def quant_rt():
            enc = codec.encode(params)
            buf, m = packing.pack_bytes(enc)
            codec.decode(packing.unpack_bytes(buf, m))
            return buf.nbytes

        t_naive = bench(naive_rt, warmup=1, iters=3, block=False)
        t_packed = bench(packed_rt, warmup=1, iters=3, block=False)
        t_quant = bench(quant_rt, warmup=1, iters=2, block=False)
        b_naive, b_packed, b_quant = naive_rt(), packed_rt(), quant_rt()
        rows.append({
            "bench": "transport", "size": size,
            "naive_s": t_naive, "packed_s": t_packed, "quant_s": t_quant,
            "naive_bytes": b_naive, "packed_bytes": b_packed,
            "quant_bytes": b_quant,
        })
        print(
            f"transport,{size},naive={t_naive*1e3:.2f}ms/{b_naive/1e6:.1f}MB,"
            f"packed={t_packed*1e3:.2f}ms/{b_packed/1e6:.1f}MB,"
            f"int8={t_quant*1e3:.2f}ms/{b_quant/1e6:.1f}MB,"
            f"wire_saving={b_naive/b_quant:.1f}x",
            flush=True,
        )
    return rows


def run_broadcast(sizes=("1m", "10m"), n_recipients=32, iters=3):
    """Serialize-once fan-out vs legacy per-send dispatch, per model size.

    ``persend`` re-serializes the pytree for every recipient (the old
    ``Channel.send`` loop, O(N·P)); ``broadcast`` serializes once straight
    off the flat numeric buffer and stamps N shared envelopes (O(P + N)).
    A bit-identity check against the per-send bytes keeps the arms honest.
    """
    rows = []
    for size in sizes:
        cfg = housing_mlp.config(size)
        params = mlp_model.init_params(jax.random.key(0), cfg)
        manifest = packing.build_manifest(params)
        numeric = packing.pack_numeric(params)
        jax.block_until_ready(numeric)

        def persend():
            ch = Channel()
            for _ in range(n_recipients):
                env = ch.send(params)
            return env

        def broadcast():
            ch = Channel()
            bc = ch.broadcast(params=params, buffer=numeric, manifest=manifest)
            for _ in range(n_recipients):
                env = bc.to()
            return env

        # honesty: both arms put identical bytes on the wire
        np.testing.assert_array_equal(
            np.asarray(persend().buffer), np.asarray(broadcast().buffer)
        )
        t_persend = bench(persend, warmup=1, iters=iters, block=False)
        t_broadcast = bench(broadcast, warmup=1, iters=iters, block=False)
        rows.append({
            "bench": "broadcast", "size": size, "recipients": n_recipients,
            "persend_s": t_persend, "broadcast_s": t_broadcast,
            "speedup_broadcast_vs_persend": t_persend / t_broadcast,
        })
        print(
            f"broadcast,{size},N={n_recipients},"
            f"persend={t_persend*1e3:.2f}ms,broadcast={t_broadcast*1e3:.2f}ms,"
            f"speedup={t_persend/t_broadcast:.1f}x",
            flush=True,
        )
    return rows


def run_upload(sizes=(2**23,), iters=2):
    """Measured uplink: raw vs int8 vs top-k sparse upload codecs.

    Each arm times **one** learner row through the channel's upload half
    (``Channel.upload`` → ``recv_upload``) and reports that upload's wire
    bytes — per-roundtrip units, same convention as :func:`run`, so MB/s is
    computable straight off the JSON row.  Honesty checks: the raw arm must
    round-trip bit-exactly; the int8 arm must stay inside the per-group
    quantization bound; the topk arms must be zero off the selected
    coordinates and exact (f32 values) or inside the quantization bound
    (int8-grouped values) on them.

    The sparse arms sweep ``k = P/16, P/64, P/256`` with f32 values plus
    ``k = P/64`` with int8-grouped values, and each row carries its byte
    ratio against the raw and int8 arms.  The contract the nightly JSON
    tracks (and this function asserts — bytes are deterministic): at
    ``k = P/64`` the topk payload is **>= 8x** smaller than raw and
    **>= 2x** smaller than the int8 codec.
    """
    rows = []
    for p in sizes:
        p = int(p)
        buf = jnp.asarray(
            np.random.default_rng(0).normal(size=(p,)).astype(np.float32)
        )
        jax.block_until_ready(buf)
        np_buf = np.asarray(buf)
        amax = float(np.max(np.abs(np_buf)))

        specs = [("raw", "raw"), ("int8", "int8")]
        for frac in (16, 64, 256):
            specs.append(
                (f"topk_p{frac}", TopkUploadCodec(k=max(1, p // frac)))
            )
        specs.append(
            ("topk_p64_q8",
             TopkUploadCodec(k=max(1, p // 64), value_dtype="int8"))
        )

        arms = {}
        for name, codec in specs:
            ch = Channel(upload_codec=codec)

            def roundtrip(ch=ch):
                env = ch.upload(buf)
                row = ch.recv_upload(env)
                jax.block_until_ready(row)
                return env

            env = roundtrip()
            got = np.asarray(ch.recv_upload(env))
            if name == "raw":
                np.testing.assert_array_equal(got, np_buf)
            elif name == "int8":
                assert float(np.max(np.abs(got - np_buf))) <= amax / 127
            else:
                idx, _ = ch.upload_codec.unpack_coords(env.payload, p)
                idx = np.asarray(idx)
                off = np.ones(p, bool)
                off[idx] = False
                assert not got[off].any()  # zero off the selected coords
                err = np.max(np.abs(got[idx] - np_buf[idx]))
                if ch.upload_codec.value_dtype == "f32":
                    assert err == 0.0
                else:
                    assert float(err) <= amax / 127

            # per-upload wire bytes off the unified telemetry surface (the
            # same counters the controller registry exposes; the assert
            # keeps them consistent with the envelope itself)
            tm = ch.telemetry
            per_upload = (tm.value("channel.upload_bytes")
                          // tm.value("channel.upload_messages"))
            assert per_upload == int(env.payload.nbytes)
            arms[name] = (bench(roundtrip, warmup=1, iters=iters, block=False),
                          int(per_upload))
        t_raw, b_raw = arms["raw"]
        t_int8, b_int8 = arms["int8"]
        saving = b_raw / b_int8
        row = {
            "bench": "upload", "p": p,
            "raw_s": t_raw, "int8_s": t_int8,
            "raw_bytes": b_raw, "int8_bytes": b_int8,
            "uplink_saving": saving,
        }
        sparse_bits = []
        for name in arms:
            if not name.startswith("topk"):
                continue
            t_k, b_k = arms[name]
            row[f"{name}_s"] = t_k
            row[f"{name}_bytes"] = b_k
            row[f"{name}_vs_raw"] = b_raw / b_k
            row[f"{name}_vs_int8"] = b_int8 / b_k
            sparse_bits.append(
                f"{name}={t_k*1e3:.2f}ms/{b_k/1e6:.3f}MB"
                f"({b_raw/b_k:.0f}x raw)"
            )
        # The headline sparse contract at k = P/64 (bytes, deterministic).
        assert row["topk_p64_vs_raw"] >= 8.0
        assert row["topk_p64_vs_int8"] >= 2.0
        rows.append(row)
        print(
            f"upload,P={p},"
            f"raw={t_raw*1e3:.2f}ms/{b_raw/1e6:.2f}MB,"
            f"int8={t_int8*1e3:.2f}ms/{b_int8/1e6:.2f}MB,"
            + ",".join(sparse_bits) +
            f",uplink_saving={saving:.2f}x",
            flush=True,
        )
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for CI (seconds, not minutes)")
    ap.add_argument("--upload", action="store_true",
                    help="run only the uplink codec arms "
                         "(raw vs int8 vs top-k sparse)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="dump result rows as JSON")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.upload:
        rows = (run_upload(sizes=(2**16,), iters=2)
                if args.smoke else run_upload())
    elif args.smoke:
        rows = run(sizes=("100k",)) + run_broadcast(sizes=("100k",),
                                                    n_recipients=8, iters=2)
    else:
        rows = run() + run_broadcast()

    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=2)
        print(f"wrote {len(rows)} rows to {args.json}", flush=True)
    return rows


if __name__ == "__main__":
    main()
