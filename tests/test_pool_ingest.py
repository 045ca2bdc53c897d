"""The device pool as a deployment runs it: a CSV job through the served
app over a pool of four devices and eight batch converters, the
per-device tile counters beside the per-device launch counters, and the
guarantee that a front-end shape launched once on a pool is ready on
every pool device. Runs on the conftest-forced 8-device CPU mesh."""
import asyncio
import threading
import urllib.parse

import jax
import numpy as np
import pytest
from aiohttp import FormData
from PIL import Image

from bucketeer_tpu import config as cfg
from bucketeer_tpu import features
from bucketeer_tpu.codec import frontend
from bucketeer_tpu.codec.pipeline import make_plan
from bucketeer_tpu.converters import TpuConverter
from bucketeer_tpu.engine import Engine, FakeS3Client, RecordingSlackClient
from bucketeer_tpu.engine import scheduler as sched_mod
from bucketeer_tpu.engine.scheduler import EncodeScheduler
from bucketeer_tpu.server.app import build_app
from bucketeer_tpu.server.metrics import Metrics

ITEMS = 4
SIZE = 256


@pytest.fixture
def compiles():
    """Backend compiles and persistent-cache loads while the test runs:
    either is a program made ready on some device."""
    seen = []

    def on_duration(event, _seconds, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(event)

    def on_event(event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            seen.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)


def _launch_and_fetch(plan, tiles, device):
    """One front-end launch on ``device`` and its payload fetch: both
    programs of an ingest chunk."""
    res = frontend.dispatch_frontend(plan, tiles,
                                     device=device).resolve_stats()
    floors = np.zeros(res.n_blocks, np.int32)
    src, _ = frontend.payload_plan(res.nbps, floors, res.layout.P)
    assert len(src)
    return frontend.fetch_payload(res, src)


def _tiles(seed):
    return np.random.default_rng(seed).integers(
        0, 256, (2, 64, 64, 3), dtype=np.uint8)


def test_a_shape_launched_once_is_ready_on_every_pool_device(compiles):
    plan = make_plan(64, 64, 3, 2, False, 8)
    tiles = _tiles(1)
    sched = EncodeScheduler(window_s=0, devices=4)
    try:
        first = sched.dispatch_frontend(plan, tiles).resolve_stats()
    finally:
        sched.close()
    floors = np.zeros(first.n_blocks, np.int32)
    src, _ = frontend.payload_plan(first.nbps, floors, first.layout.P)
    want = frontend.fetch_payload(first, src)
    del compiles[:]
    for device in jax.devices()[1:4]:
        got = _launch_and_fetch(plan, tiles, device)
        np.testing.assert_array_equal(got, want)
    assert compiles == []


def test_off_the_pool_another_device_compiles(compiles):
    """The control of the case above: a device outside any pool makes
    its programs on its first launch, and the listener sees it."""
    plan = make_plan(64, 64, 3, 1, False, 8)
    tiles = _tiles(2)
    _launch_and_fetch(plan, tiles, jax.devices()[0])
    del compiles[:]
    _launch_and_fetch(plan, tiles, jax.devices()[5])
    assert compiles


def test_one_device_pool_readies_nothing_beyond_its_device(monkeypatch):
    readied = []
    monkeypatch.setattr(frontend, "_ready_on_pool",
                        lambda *args: readied.append(args))
    sched = EncodeScheduler(window_s=0, devices=1)
    try:
        sched.dispatch_frontend(make_plan(64, 64, 3, 2, False, 8),
                                _tiles(3)).resolve_stats()
    finally:
        sched.close()
    assert readied == []


def test_per_device_tiles_sum_to_the_family_and_idle_devices_read_zero():
    sched = EncodeScheduler(window_s=0, devices=4)
    sched.launch_fn = lambda plan, tiles, mode="rows": ("pending", plan)
    sink = Metrics()
    sched.set_metrics_sink(sink)
    try:
        sched.dispatch_frontend(("p",), np.zeros((3, 2, 2, 3), np.uint8))
    finally:
        sched.close()
    counters = sink.report()["counters"]
    per_dev = {k: v for k, v in counters.items()
               if k.startswith("encode.batched_tiles.d")}
    assert sorted(per_dev) == [f"encode.batched_tiles.d{i}"
                               for i in range(4)]
    assert sum(per_dev.values()) == counters["encode.batched_tiles"] == 3


def _photo(seed):
    rng = np.random.default_rng(seed)
    y, x = np.mgrid[0:SIZE, 0:SIZE]
    base = 120 + 80 * np.sin(x / 17.0 + seed) * np.cos(y / 13.0)
    img = base[..., None] + rng.normal(0, 8, (SIZE, SIZE, 3))
    return np.clip(img, 0, 255).astype(np.uint8)


def _csv_form(job):
    rows = ["Item ARK,File Name"] + [f"ark:/1/{job}{i},m{i}.tif"
                                     for i in range(ITEMS)]
    form = FormData()
    form.add_field("csvFileToUpload", ("\n".join(rows) + "\n").encode(),
                   filename=f"{job}.csv", content_type="text/csv")
    form.add_field("slack-handle", "tester")
    return form


async def _csv_job(tmp_path, aiohttp_client, job, devices):
    """One CSV job of ITEMS lossy masters through the served app, its
    converter on a private pool of ``devices``. Returns (derivatives by
    item, /metrics counter deltas, encode.t1_wait records)."""
    config = cfg.Config.load(overrides={
        cfg.IIIF_URL: "http://iiif.test/iiif",
        cfg.SLACK_CHANNEL_ID: "chan",
        cfg.FILESYSTEM_CSV_MOUNT: str(tmp_path / "csv-mount"),
        cfg.FILESYSTEM_IMAGE_MOUNT: str(tmp_path),
        cfg.S3_REQUEUE_DELAY: 0.01,
        cfg.CONVERSION_TYPE: "lossy",
        cfg.SCHED_DEVICES: devices,
        "batch.converter.instances": 8})
    sched = EncodeScheduler(window_s=0)
    shared = sched_mod.get_scheduler()
    shared_devices = shared.devices
    s3_dir = tmp_path / f"s3-{job}"
    try:
        engine = Engine(
            config,
            flags=features.FeatureFlagChecker(
                static={features.FS_WRITE_CSV: True}),
            converter=TpuConverter(scheduler=sched),
            s3_client=FakeS3Client(str(s3_dir)),
            slack_client=RecordingSlackClient())
        app = build_app(engine, job_delete_timeout=0.1)
        sched.set_metrics_sink(app["api"].metrics)
        client = await aiohttp_client(app)
        before = await (await client.get("/metrics")).json()
        resp = await client.post("/batch/input/csv", data=_csv_form(job))
        assert resp.status == 200, await resp.text()
        out = tmp_path / "csv-mount" / f"{job}.csv"
        for _ in range(3000):
            if out.exists():
                break
            await asyncio.sleep(0.05)
        assert out.read_text().count("succeeded") == ITEMS
        after = await (await client.get("/metrics")).json()
    finally:
        sched.close()
        # The engine caps the process-wide pool too; leave it as found.
        shared.configure(devices=shared_devices)
    assert sched.devices == devices
    found = {}
    for path in s3_dir.rglob("*.jpx"):
        item = urllib.parse.unquote(path.stem).rsplit("/", 1)[1]
        found[item[len(job):]] = path.read_bytes()
    deltas = {k: v - before.get("counters", {}).get(k, 0)
              for k, v in after["counters"].items()}
    waits = (after["stages"]["encode.t1_wait"]["count"]
             - before.get("stages", {}).get("encode.t1_wait",
                                            {}).get("count", 0))
    return found, deltas, waits


async def test_csv_job_over_a_four_device_pool_matches_one_device(
        tmp_path, aiohttp_client, monkeypatch):
    """The deployment of the four-chip benchmark cell at a test's size:
    eight converters share a pool of four devices. The derivatives are
    byte-identical to the same job on a pool of one, at least two
    devices launch, and the per-device tile counters sum to the
    family's."""
    monkeypatch.setenv("BUCKETEER_TMPDIR", str(tmp_path))
    for i in range(ITEMS):
        Image.fromarray(_photo(i)).save(tmp_path / f"m{i}.tif")
    # One tile a launch, and the first launch held until a second has
    # started: the spread over devices is then certain, not a race.
    monkeypatch.setitem(sched_mod._STAGE_CAPS, "frontend", 1)
    second = threading.Event()
    launches = []
    dispatch = frontend.dispatch_frontend

    def overlapped(*args, **kwargs):
        launches.append(kwargs.get("device"))
        if len(launches) == 1:
            second.wait(timeout=60)
        else:
            second.set()
        return dispatch(*args, **kwargs)

    one, _, _ = await _csv_job(tmp_path, aiohttp_client, "solo", 1)
    monkeypatch.setattr(frontend, "dispatch_frontend", overlapped)
    four, deltas, waits = await _csv_job(tmp_path, aiohttp_client,
                                         "pool", 4)
    assert sorted(one) == sorted(four) == [str(i) for i in range(ITEMS)]
    assert one == four
    launched = {k for k, v in deltas.items()
                if k.startswith("encode.device_launches.d") and v > 0}
    assert len(launched) >= 2, deltas
    tiles = {k: v for k, v in deltas.items()
             if k.startswith("encode.batched_tiles.d")}
    assert sorted(tiles) == [f"encode.batched_tiles.d{i}"
                             for i in range(4)]
    assert sum(tiles.values()) == deltas["encode.batched_tiles"] == ITEMS
    assert waits >= ITEMS
