"""graftscope through the product surface: the traced HTTP stack
(request ids, span trees for region reads, flight/trace debug
endpoints, Prometheus format, SLO breach handling) and the
merged-device-launch span links through the real scheduler."""
import json
import logging
import threading

import numpy as np
import pytest

from bucketeer_tpu import config as cfg
from bucketeer_tpu import features
from bucketeer_tpu import obs
from bucketeer_tpu.codec import encoder as codec_encoder
from bucketeer_tpu.codec.encoder import EncodeParams
from bucketeer_tpu.converters import output_path
from bucketeer_tpu.engine import Engine, FakeS3Client, RecordingSlackClient
from bucketeer_tpu.obs import logctx
from bucketeer_tpu.server.app import build_app


@pytest.fixture
def fresh_obs():
    """A fresh recorder for the app to adopt (Api's maybe_install keeps
    an existing one), torn down afterwards so later tests see the
    disabled fast path."""
    obs.install(None)
    logctx.uninstall()
    try:
        yield
    finally:
        obs.install(None)
        logctx.uninstall()


@pytest.fixture
def env_client(tmp_path, aiohttp_client, fresh_obs):
    """(http client, engine) factory — the test_api harness, local to
    this module (fixtures don't import across test files)."""

    async def factory(extra_config=None):
        overrides = {
            cfg.IIIF_URL: "http://iiif.test/iiif",
            cfg.SLACK_CHANNEL_ID: "chan",
            cfg.FILESYSTEM_CSV_MOUNT: str(tmp_path / "csv-mount"),
        }
        overrides.update(extra_config or {})
        config = cfg.Config.load(overrides=overrides)
        engine = Engine(
            config,
            flags=features.FeatureFlagChecker(static={}),
            converter=None,
            s3_client=FakeS3Client(str(tmp_path / "s3")),
            slack_client=RecordingSlackClient())
        app = build_app(engine, job_delete_timeout=0.1)
        client = await aiohttp_client(app)
        return client, engine

    return factory


def _write_derivative(tmp_path, monkeypatch, image_id="ark:/9/obs",
                      size=64):
    monkeypatch.setenv("BUCKETEER_TMPDIR", str(tmp_path))
    rng = np.random.default_rng(11)
    img = rng.integers(0, 256, size=(size, size, 3)).astype(np.uint8)
    data = codec_encoder.encode_jp2(
        img, 8, EncodeParams(lossless=True, levels=2, tile_size=size,
                             gen_plt=True), jpx=True)
    with open(output_path(image_id, ".jpx"), "wb") as fh:
        fh.write(data)
    return img


async def test_region_read_yields_complete_span_tree(
        env_client, tmp_path, monkeypatch):
    """Acceptance (ISSUE 14): one GET /images/{id}?region=... request
    produces a complete exported span tree — HTTP root -> admitted
    read (queue wait) -> decode — with the same request id on every
    span, honored from the inbound X-Request-Id header and echoed in
    the response; the export is valid Chrome-trace JSON."""
    _write_derivative(tmp_path, monkeypatch)
    client, _ = await env_client()

    resp = await client.get(
        "/images/ark:%2F9%2Fobs?region=0,0,32,32&format=raw",
        headers={"X-Request-Id": "acc-1"})
    assert resp.status == 200
    assert resp.headers["X-Request-Id"] == "acc-1"

    trace = await client.get("/debug/trace/acc-1")
    assert trace.status == 200
    doc = json.loads(await trace.text())
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    names = {e["name"] for e in xs}
    # HTTP root -> handler stage -> scheduler queue wait -> decode job.
    assert {"http.get_image", "image_read", "decode.queue_wait",
            "decode.read"} <= names, names
    for e in xs:
        assert e["args"]["request_id"] == "acc-1", e
    # Parent links resolve within the tree: everything hangs off the
    # HTTP root.
    ids = {e["args"]["span_id"] for e in xs}
    roots = [e for e in xs if "parent_id" not in e["args"]]
    assert [e["name"] for e in roots] == ["http.get_image"]
    for e in xs:
        if "parent_id" in e["args"]:
            assert e["args"]["parent_id"] in ids, e
    # Structural Chrome-trace contract.
    for e in doc["traceEvents"]:
        assert e["ph"] in ("X", "M")
        if e["ph"] == "X":
            assert e["ts"] >= 0 and e["dur"] >= 0


async def test_error_path_stamps_logs_and_dumps_flight(
        env_client, tmp_path, monkeypatch, caplog):
    """A 5xx outcome auto-freezes the flight recorder with the request
    id, and the log lines the request emitted carry the same id."""
    monkeypatch.setenv("BUCKETEER_TMPDIR", str(tmp_path))
    with open(output_path("ark:/9/bad", ".jpx"), "wb") as fh:
        fh.write(b"not a jp2 at all")
    client, _ = await env_client()

    with caplog.at_level(logging.WARNING):
        resp = await client.get("/images/ark:%2F9%2Fbad",
                                headers={"X-Request-Id": "err-7"})
    assert resp.status == 500
    assert resp.headers["X-Request-Id"] == "err-7"
    decode_logs = [r for r in caplog.records
                   if "decode failed" in r.message]
    assert decode_logs, "expected the handler's decode-failure log"
    for record in decode_logs:
        assert record.request_id == "err-7"

    flight = await client.get("/debug/flight")
    report = json.loads(await flight.text())
    assert report["enabled"] is True
    reasons = {(d["reason"], d["request_id"]) for d in report["dumps"]}
    assert ("error:get_image", "err-7") in reasons, reasons


async def test_slo_breach_triggers_flight_dump(env_client):
    """Test-pinned acceptance: an SLO breach bumps the breach counters
    and freezes the flight recorder."""
    client, _ = await env_client(
        extra_config={cfg.SLO: "default=0.000001"})
    resp = await client.get("/status")
    assert resp.status == 200
    rid = resp.headers["X-Request-Id"]
    assert rid                       # generated when not supplied

    metrics = json.loads(await (await client.get("/metrics")).text())
    counters = metrics["counters"]
    assert counters["slo.breaches"] >= 1
    assert counters["slo.breach.get_status"] >= 1
    assert metrics["slo"]["default_ms"] == pytest.approx(1e-6)

    report = json.loads(await (await client.get("/debug/flight")).text())
    assert any(d["reason"] == "slo-breach:get_status"
               for d in report["dumps"]), report["dumps"]


async def test_metrics_formats_and_endpoint_percentiles(env_client):
    client, _ = await env_client()
    await client.get("/status")
    await client.get("/status")

    rep = json.loads(await (await client.get("/metrics")).text())
    status_stage = rep["stages"]["http.get_status"]
    assert status_stage["count"] >= 2
    for key in ("p50_ms", "p95_ms", "p99_ms"):
        assert key in status_stage

    prom = await client.get("/metrics?format=prometheus")
    assert prom.status == 200
    assert prom.content_type == "text/plain"
    text = await prom.text()
    assert "# TYPE bucketeer_stage_seconds histogram" in text
    assert 'bucketeer_stage_seconds_bucket{stage="http.get_status"' \
        in text
    assert 'le="+Inf"' in text
    assert 'bucketeer_stage_seconds_count{stage="http.get_status"}' \
        in text

    assert (await client.get("/metrics?format=bogus")).status == 400


async def test_flight_endpoint_freeze_and_fetch(env_client):
    client, _ = await env_client()
    await client.get("/status")
    report = json.loads(
        await (await client.get("/debug/flight?freeze=1")).text())
    assert report["enabled"] is True
    assert report["dumps"], report
    seq = report["dumps"][-1]["seq"]
    entry = json.loads(
        await (await client.get(f"/debug/flight?dump={seq}")).text())
    assert entry["seq"] == seq
    assert isinstance(entry["spans"], list)
    assert (await client.get("/debug/flight?dump=xyz")).status == 400
    assert (await client.get("/debug/flight?dump=99999")).status == 404
    assert (await client.get("/debug/trace/nope-absent")).status == 404


def test_merged_launch_span_links_both_requests():
    """Acceptance (ISSUE 14): a device launch that merges chunks from
    two requests yields ONE launch span, linked to both request
    contexts, carrying occupancy and the graftcost-modeled cost; each
    request's Chrome export includes the shared launch span."""
    from bucketeer_tpu.engine.scheduler import (EncodeScheduler,
                                                _SlicedPending)
    from bucketeer_tpu.obs.trace import Recorder

    class FakePending:
        def __init__(self, n):
            self.n = n

        def resolve_stats(self, tile_off=0, n_tiles=None):
            return ("stats", tile_off, n_tiles)

    def stub_launch(plan, tiles, mode="rows"):
        return FakePending(len(tiles))

    prev = obs.get_recorder()
    for attempt in range(5):
        rec = Recorder()
        obs.install(rec)
        try:
            sched = EncodeScheduler(window_s=0.5, max_concurrent=4)
            sched.launch_fn = stub_launch
            plan = ("plan", 4, 4)
            tiles = np.zeros((1, 4, 4, 3), dtype=np.uint8)
            results = {}
            barrier = threading.Barrier(2)

            def client(i):
                with obs.request_context(f"req-{i}"):
                    barrier.wait()
                    results[i] = sched.submit(
                        lambda: sched.dispatch_frontend(plan, tiles))

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(2)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            sched.close()

            launches = [s for s in rec.snapshot()
                        if s["name"] == "device.launch"]
            assert launches, "no launch span recorded"
            merged = [s for s in launches
                      if s["attrs"]["occupancy"] == 2]
            if not merged:
                continue      # unlucky schedule: retry the merge
            (launch,) = merged
            linked = {link[0] for link in launch["links"]}
            assert linked == {"req-0", "req-1"}, launch["links"]
            assert launch["attrs"]["tiles"] == 2
            assert launch["attrs"]["mode"] == "rows"
            assert launch["attrs"]["device_id"] == 0
            assert launch["dur"] >= 0
            # Both requests got sliced views of the one merged launch.
            assert {type(r) for r in results.values()} == {
                _SlicedPending}
            for i in range(2):
                doc = obs.chrome_trace(f"req-{i}")
                names = {e["name"] for e in doc["traceEvents"]
                         if e["ph"] == "X"}
                assert {"encode.queue_wait", "device.launch"} <= names
            return
        finally:
            obs.install(prev)
    raise AssertionError("no merged (occupancy=2) launch in 5 attempts")


def test_real_encode_span_tree_through_scheduler():
    """A real (tiny) encode through the scheduler with tracing on:
    dispatch, host Tier-1 pool item, reassembly and Tier-2 spans all
    appear under the request's trace — the encode-side span coverage
    the flight recorder shows in production."""
    from bucketeer_tpu.engine.scheduler import EncodeScheduler
    from bucketeer_tpu.obs.trace import Recorder

    prev = obs.get_recorder()
    rec = Recorder()
    obs.install(rec)
    try:
        sched = EncodeScheduler(window_s=0.0)
        img = np.linspace(0, 255, 64 * 64 * 3).reshape(
            64, 64, 3).astype(np.uint8)
        with obs.request_context("enc-1"):
            out = sched.encode_jp2(img, 8, EncodeParams(
                lossless=True, levels=2))
        sched.close()
        assert out[:4] == b"\x00\x00\x00\x0c"      # JP2 signature box
        mine = {s["name"] for s in rec.spans_for("enc-1")}
        assert {"encode.queue_wait", "encode.dispatch",
                "encode.resolve_stats", "encode.host_t1",
                "encode.reassemble", "encode.tier2"} <= mine, mine
        # The pool item ran on a sched-t1 thread yet joined the trace.
        host = [s for s in rec.spans_for("enc-1")
                if s["name"] == "encode.host_t1"]
        assert any(s["thread"].startswith("sched-t1") for s in host)
    finally:
        obs.install(prev)
