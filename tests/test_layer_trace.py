"""Layer spans on the profiler's clock: the spans named in
``obs.LAYER_SPANS`` enter a ``jax.profiler`` trace and the
request-enclosing ones stay out; the /metrics stages those spans feed
as they close (host Tier-1 per chunk, MQ decode per tile); and the
benchmark's readers of those stages (``perfbench/metrics``)."""
import glob
import importlib.util
import os
import re

import jax
import numpy as np
import pytest
from PIL import Image

from bucketeer_tpu import obs
from bucketeer_tpu.codec import encoder
from bucketeer_tpu.codec import rate as rate_mod
from bucketeer_tpu.codec.decode import decode, decoder
from bucketeer_tpu.codec.encoder import EncodeParams
from bucketeer_tpu.converters import Conversion, TpuConverter
from bucketeer_tpu.engine.scheduler import EncodeScheduler
from bucketeer_tpu.obs.trace import Recorder
from bucketeer_tpu.server.metrics import Metrics

METRICS_DIR = os.path.join(os.path.dirname(__file__), os.pardir,
                           "perfbench", "metrics")

# Spans that enclose a whole request: each would overlap every idle gap
# inside it, so none may reach the profiler's trace.
REQUEST_ENCLOSING = {"convert.encode", "decode.read", "batch.item",
                     "batchread.item", "encode.request", "decode.request",
                     "s3.put"}


def _photo(rng, h, w, comps=3):
    y, x = np.mgrid[0:h, 0:w]
    base = 120 + 80 * np.sin(x / 17.0) * np.cos(y / 13.0)
    img = base[..., None] + rng.normal(0, 8, (h, w, comps))
    return np.clip(img, 0, 255).astype(np.uint8)


@pytest.fixture
def sink(monkeypatch):
    m = Metrics()
    monkeypatch.setattr(encoder, "_metrics_sink", m)
    monkeypatch.setattr(decoder, "_metrics_sink", m)
    return m


def _host_event_names(trace_dir: str) -> set:
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    return {event.name for plane in data.planes
            if plane.name.startswith("/host:CPU")
            for line in plane.lines for event in line.events}


def test_layer_spans_enter_the_profiler_trace(rng, tmp_path, monkeypatch,
                                              sink):
    """A convert through the scheduler and a region read through it,
    traced by jax.profiler: every layer span is a host event of the
    trace, and no request-enclosing span is."""
    monkeypatch.setenv("BUCKETEER_TMPDIR", str(tmp_path))
    img = _photo(rng, 96, 128)
    src = str(tmp_path / "master.tif")
    Image.fromarray(img).save(src)
    prev = obs.get_recorder()
    obs.install(Recorder())
    sched = EncodeScheduler(window_s=0.0)
    try:
        with jax.profiler.trace(str(tmp_path / "trace")):
            with obs.request_context("layer-1"):
                out = TpuConverter(scheduler=sched).convert(
                    "layer-1", src, Conversion.LOSSLESS)
                with open(out, "rb") as fh:
                    data = fh.read()
                with obs.span("decode.read"):
                    tile = sched.read(decode, data, region=(16, 8, 64, 48))
    finally:
        sched.close()
        obs.install(prev)
    np.testing.assert_array_equal(tile, img[8:56, 16:80])
    names = _host_event_names(str(tmp_path / "trace"))
    assert obs.LAYER_SPANS <= names, sorted(obs.LAYER_SPANS - names)
    assert not names & REQUEST_ENCLOSING, sorted(names & REQUEST_ENCLOSING)
    assert not {n for n in names if n.startswith("http.")}


def _encode_with_a_floor_rerun(rng, monkeypatch) -> tuple:
    """A 256^2 lossy encode in chunks of two 64^2 tiles whose first
    floors code nothing, so every chunk is coded twice. Returns (h, w,
    chunks)."""
    monkeypatch.setenv("BUCKETEER_OVERLAP_TILES", "2")
    real = rate_mod.estimate_floors
    calls = []

    def starve_first(nbps, *args, **kwargs):
        floors, lam = real(nbps, *args, **kwargs)
        calls.append(len(calls))
        if len(calls) == 1:
            # Floors at every block's top plane code nothing, so the
            # supply falls short of the target and the floors rerun.
            floors = np.asarray(nbps, dtype=floors.dtype).copy()
        return floors, lam

    monkeypatch.setattr(rate_mod, "estimate_floors", starve_first)
    h, w = 256, 256
    img = _photo(rng, h, w)
    data = encoder.encode_jp2(img, 8, EncodeParams(
        lossless=False, levels=3, tile_size=64, rate=2.0, n_layers=3,
        base_delta=0.5))
    assert decode(data).shape == img.shape
    return h, w, (h // 64) * (w // 64) // 2


def test_host_code_records_each_chunk_and_counts_pixels_once(
        rng, monkeypatch, sink):
    """encode.host_code is recorded per chunk as it finishes; a floor
    rerun adds records and time but no pixels, so the stage's pixels
    are the image's once. encode.fetch_payload counts the same way, and
    the native call is its own stage inside each chunk."""
    h, w, chunks = _encode_with_a_floor_rerun(rng, monkeypatch)
    assert sink.counters["encode.floor_reruns"] == 1
    for name in ("encode.host_code", "encode.fetch_payload", "t1.native"):
        assert sink.stages[name].count == 2 * chunks, name
    for name in ("encode.host_code", "encode.fetch_payload",
                 "encode.tier2"):
        assert sink.stages[name].pixels == h * w, name
    assert sink.stages["t1.native"].total_s <= \
        sink.stages["encode.host_code"].total_s


def test_t1_wait_records_each_chunk_and_counts_pixels_once(
        rng, monkeypatch, sink):
    """encode.t1_wait, a chunk's wait for a worker of the Tier-1 pool,
    is recorded once per Tier-1 chunk as its host Tier-1 starts, with
    the chunk's pixels on its first attempt only; its span belongs to
    the request and stays out of the profiler's layer spans."""
    prev = obs.get_recorder()
    rec = Recorder()
    obs.install(rec)
    try:
        with obs.request_context("wait-1"):
            h, w, chunks = _encode_with_a_floor_rerun(rng, monkeypatch)
    finally:
        obs.install(prev)
    waits = sink.stages["encode.t1_wait"]
    assert waits.count == sink.stages["encode.host_code"].count == 2 * chunks
    assert waits.pixels == h * w
    spans = [s for s in rec.snapshot() if s["name"] == "encode.t1_wait"]
    assert len(spans) == 2 * chunks
    assert {s["trace_id"] for s in spans} == {"wait-1"}
    assert sorted(s["attrs"]["pixels"] for s in spans) == \
        [0] * chunks + [2 * 64 * 64] * chunks
    assert "encode.t1_wait" not in obs.LAYER_SPANS


def _stages_read(prefix: str) -> set:
    """The stage names starting with ``prefix`` that the benchmark's
    metric readers read."""
    names = set()
    for path in glob.glob(os.path.join(METRICS_DIR, "*.py")):
        with open(path, encoding="utf-8") as fh:
            names.update(re.findall(r'stage\(ctx, "([^"]+)"\)', fh.read()))
    return {n for n in names if n.startswith(prefix)}


# Encode stages /metrics shows that no benchmark reader reads.
UNREAD_ENCODE_STAGES = {"encode.device_dispatch", "encode.request"}


@pytest.mark.parametrize("fields", [
    {"lossless": True, "levels": 3},
    {"lossless": False, "levels": 3, "rate": 2.0, "n_layers": 3,
     "base_delta": 0.5},
], ids=["streaming_lossless", "rate_targeted"])
def test_encode_records_the_stages_ingest_reads(rng, sink, monkeypatch,
                                                fields):
    """An encode through the scheduler records exactly the encode.*
    stages the ingest readers read, besides the two no reader reads:
    every Tier-1 chunk takes the one host path, and no other path's
    stages appear."""
    monkeypatch.setenv("BUCKETEER_OVERLAP_TILES", "2")
    sched = EncodeScheduler(window_s=0.0)
    sched.set_metrics_sink(sink)
    img = _photo(rng, 128, 128)
    try:
        data = sched.encode_jp2(img, 8, EncodeParams(tile_size=64,
                                                     **fields))
    finally:
        sched.close()
    assert decode(data).shape == img.shape
    recorded = {n for n in sink.stages if n.startswith("encode.")}
    read = _stages_read("encode.")
    assert read >= {"encode.host_code", "encode.fetch_payload",
                    "encode.tier2", "encode.t1_wait", "encode.queue_wait"}
    assert recorded == read | UNREAD_ENCODE_STAGES
    for name in ("encode.host_code", "encode.fetch_payload",
                 "encode.tier2"):
        assert sink.stages[name].pixels == 128 * 128, name
    assert sink.stages["t1.native"].count == \
        sink.stages["encode.host_code"].count


def test_decode_records_mq_once_per_tile(rng, sink):
    """decode.mq and decode.t1 are recorded per decoded tile; the
    device inverse once per launch: one per shape group in a full
    decode, one per tile in a region read."""
    img = _photo(rng, 128, 128)
    data = encoder.encode_jp2(img, 8, EncodeParams(
        lossless=True, levels=3, tile_size=64))
    decode(data)
    assert sink.stages["decode.mq"].count == 4
    assert sink.stages["decode.t1"].count == 4
    assert sink.stages["decode.t2_parse"].count == 1
    assert sink.stages["decode.device_inverse"].count == 1
    assert sink.stages["decode.device_inverse"].pixels == 128 * 128
    region = decode(data, region=(32, 16, 64, 32))
    np.testing.assert_array_equal(region, img[16:48, 32:96])
    assert sink.stages["decode.mq"].count == 4 + 2
    assert sink.stages["decode.device_inverse"].count == 1 + 2
    assert sink.stages["decode.device_inverse"].pixels == \
        128 * 128 + 64 * 32


def _ctx(**stages) -> dict:
    return {"stages": stages, "summary": {"reads_served": 8}}


FULL = {
    "encode.host_code": {"count": 4, "sum_s": 2.0, "mpixels": 8.0},
    "t1.native": {"count": 4, "sum_s": 1.5},
    "encode.fetch_payload": {"count": 4, "sum_s": 0.4, "mpixels": 8.0},
    "encode.tier2": {"count": 2, "sum_s": 0.8, "mpixels": 8.0},
    "convert.tiff_read": {"count": 2, "sum_s": 0.16, "mpixels": 8.0},
    "s3.put": {"count": 5, "sum_s": 0.5},
    "decode.device_inverse": {"count": 12, "sum_s": 1.2},
    "encode.t1_wait": {"count": 4, "sum_s": 0.2, "mpixels": 8.0},
}


@pytest.mark.parametrize("name,expected,needs", [
    ("t1_native_ms_per_mpix", 187.5, ("t1.native", "encode.host_code")),
    ("t1_glue_ms_per_mpix", 62.5, ("t1.native", "encode.host_code")),
    ("payload_fetch_ms_per_mpix", 50.0, ("encode.fetch_payload",)),
    ("tier2_ms_per_mpix", 100.0, ("encode.tier2",)),
    ("tiff_read_ms_per_mpix", 20.0, ("convert.tiff_read",)),
    ("s3_put_ms", 100.0, ("s3.put",)),
    ("inverse_host_ms_per_read", 150.0, ("decode.device_inverse",)),
    ("t1_wait_ms_per_mpix", 25.0, ("encode.t1_wait",)),
])
def test_layer_metric_reader(monkeypatch, name, expected, needs):
    """Each reader's value from a hand-made window, and None (the
    metric left out of the line) when a stage it reads is absent, as
    on a program that lacks the span."""
    monkeypatch.syspath_prepend(os.path.dirname(METRICS_DIR))
    spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + name,
        os.path.join(METRICS_DIR, f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    assert module.read(_ctx(**FULL)) == pytest.approx(expected)
    for stage_name in needs:
        partial = {k: v for k, v in FULL.items() if k != stage_name}
        assert module.read(_ctx(**partial)) is None, stage_name
