"""The per-layer readers of the program's telemetry, on made-up snapshots."""

import json
import types

import pytest

from bench import counts, device, spans, spec, trace

HIST = {"count": 0, "sum": 0.0, "min": 0.0, "max": 0.0, "last": 0.0}

#: The per-layer metrics of ``BENCHMARK.json`` that read one program span.
SPAN_READERS = [m["name"] for m in json.loads((spec.ROOT / "BENCHMARK.json").read_text())["per_layer"]
                if getattr(spec.metric_reader(m["name"]), "SPANS", ())]


def _ctx(before: dict, after: dict):
    return types.SimpleNamespace(before=before, after=after)


@pytest.mark.parametrize("name", SPAN_READERS)
def test_a_span_reader_reads_its_spans_window_mean(name):
    span, = spec.metric_reader(name).SPANS
    before = {span: dict(HIST, count=10, sum=1.0), "other.span": dict(HIST, count=1, sum=9.0)}
    after = {span: dict(HIST, count=14, sum=1.2), "other.span": dict(HIST, count=9, sum=99.0)}
    # 4 calls in the window took 0.2 s: 50 ms each.
    assert spec.metric_reader(name).read(_ctx(before, after)) == pytest.approx(50.0)


@pytest.mark.parametrize("name", SPAN_READERS + ["upload_encode_gbps"])
def test_a_reader_with_nothing_to_read_returns_nothing(name):
    quiet = {span: dict(HIST, count=3, sum=0.3) for span in spans.METRICS.values()}
    quiet.update({"channel.upload_bytes": 100, "channel.upload_serialize_s": 0.5})
    assert spec.metric_reader(name).read(_ctx(quiet, quiet)) is None
    assert spec.metric_reader(name).read(_ctx({}, {})) is None


def test_upload_encode_gbps_is_payload_bytes_over_encode_seconds():
    before = {"channel.upload_bytes": 1_000_000_000, "channel.upload_serialize_s": 2.0}
    after = {"channel.upload_bytes": 9_000_000_000, "channel.upload_serialize_s": 12.0}
    got = spec.metric_reader("upload_encode_gbps").read(_ctx(before, after))
    assert got == pytest.approx(0.8)


def _reduce_ctx(shards: int, devices: int, w):
    """Two rounds' reduces, each one execution of 4 ms on every device."""
    calls = 2 * devices
    summary = trace.Summary(window_s=30.0, busy_s=0.1, devices=devices,
                            program_s={"jit_masked_weighted_average": 0.004 * calls},
                            program_calls={"jit_masked_weighted_average": calls},
                            idle_by_span={})
    return types.SimpleNamespace(
        w=w, dev={"kind": "TPU v5 lite"}, trace=summary,
        shapes=counts.Shapes(rows=16, width=4 * 2**24, arena_dtype="f32", shards=shards),
        peak=lambda key: float(device.peaks("TPU v5 lite")[key]))


def test_reduce_roofline_holds_each_shard_to_its_own_roofline():
    """Each device's execution is held to its own shard's bytes.

    Four shards, each reduced in 4 ms on its device, read a quarter of the
    whole arena reduced in 4 ms on one.
    """
    reader = spec.metric_reader("reduce_roofline")
    w = types.SimpleNamespace(chips=4)
    whole = reader.read(_reduce_ctx(1, 1, w))
    sharded = reader.read(_reduce_ctx(4, 4, w))
    assert sharded == pytest.approx(whole / 4)
    # 16 rows of 2**24 f32 columns and the output row in 4 ms at 819 GB/s.
    assert sharded == pytest.approx(100 * 17 * 2**24 * 4 / 819e9 / 0.004)


def test_round_mfu_divides_by_every_chip_of_the_cell():
    reader = spec.metric_reader("round_mfu")
    ctx = types.SimpleNamespace(window_s=10.0, flops=lambda: 197e12,
                                peak=lambda key: float(device.peaks("TPU v5 lite")[key]))
    ctx.w = types.SimpleNamespace(chips=1)
    assert reader.read(ctx) == pytest.approx(10.0)
    ctx.w = types.SimpleNamespace(chips=4)
    assert reader.read(ctx) == pytest.approx(2.5)
