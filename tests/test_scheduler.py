"""Cross-request encode scheduler (engine/scheduler.py): byte-identity
under concurrency (the hard contract — merged device launches and the
shared host Tier-1 pool must not change a single output byte), admission
control / priority / deadlines, and failure isolation (a dead request
never poisons a shared device batch)."""
import threading
import time

import numpy as np
import pytest

from bucketeer_tpu.codec import encoder
from bucketeer_tpu.codec.encoder import EncodeParams
from bucketeer_tpu.codec.pipeline import make_plan
from bucketeer_tpu.engine.scheduler import (
    PRIORITY_BATCH, PRIORITY_SINGLE, DeadlineExceeded, EncodeScheduler,
    QueueFull, get_scheduler)
from bucketeer_tpu.server.metrics import Metrics


def _images(n, size, seed=5, bitdepth=8):
    rng = np.random.default_rng(seed)
    dtype = np.uint8 if bitdepth == 8 else np.uint16
    return [rng.integers(0, 1 << bitdepth, (size, size, 3), dtype=dtype)
            for _ in range(n)]


def _concurrent(sched, imgs, params, bitdepth=8):
    outs = [None] * len(imgs)
    errs = [None] * len(imgs)
    barrier = threading.Barrier(len(imgs))

    def client(i):
        barrier.wait()
        try:
            outs[i] = sched.encode_jp2(imgs[i], bitdepth, params)
        except BaseException as exc:          # surfaced to the test
            errs[i] = exc

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(imgs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return outs, errs


@pytest.fixture
def sched():
    s = EncodeScheduler(queue_depth=16, max_concurrent=4, pool_size=2,
                        window_s=0.2)
    yield s
    s.close()


# --- byte-identity under concurrency ---------------------------------

@pytest.mark.parametrize("bitdepth", [8, 16])
def test_concurrent_lossless_bytes_identical(sched, bitdepth):
    imgs = _images(4, 64, seed=11, bitdepth=bitdepth)
    params = EncodeParams(lossless=True, levels=3)
    serial = [encoder.encode_jp2(im, bitdepth, params) for im in imgs]
    outs, errs = _concurrent(sched, imgs, params, bitdepth)
    assert errs == [None] * 4
    assert outs == serial


@pytest.mark.parametrize("bitdepth", [8, 16])
def test_concurrent_rate_targeted_bytes_identical(sched, bitdepth):
    # The base step scales with the signal range, as the converter
    # scales it for deeper scans.
    imgs = _images(3, 96, seed=12, bitdepth=bitdepth)
    params = EncodeParams(lossless=False, levels=3,
                          base_delta=2.0 * (1 << (bitdepth - 8)), rate=1.5)
    serial = [encoder.encode_jp2(im, bitdepth, params) for im in imgs]
    outs, errs = _concurrent(sched, imgs, params, bitdepth)
    assert errs == [None] * 3
    assert outs == serial


def test_tiled_multichunk_through_scheduler(sched, monkeypatch):
    monkeypatch.setenv("BUCKETEER_OVERLAP_TILES", "2")
    img = _images(1, 128, seed=13)[0]
    params = EncodeParams(lossless=False, levels=3, tile_size=64,
                          base_delta=2.0, rate=1.8)
    serial = encoder.encode_jp2(img, 8, params)
    assert sched.encode_jp2(img, 8, params) == serial


def test_merged_launch_occupancy_and_metrics():
    # devices=1 pins a single-worker pool: with free peer devices the
    # scheduler prefers parallelism over merging, and this test is
    # about the merge path (tests/test_scheduler_pool.py covers the
    # multi-device spread).
    sched = EncodeScheduler(queue_depth=16, max_concurrent=4,
                            pool_size=2, window_s=0.2, devices=1)
    sink = Metrics()
    sched.set_metrics_sink(sink)
    try:
        imgs = _images(4, 64, seed=14)
        params = EncodeParams(lossless=True, levels=3)
        serial = [encoder.encode_jp2(im, 8, params) for im in imgs]
        outs, errs = _concurrent(sched, imgs, params)
        assert errs == [None] * 4 and outs == serial
        rep = sink.report()
        occ = rep["values"]["encode.batch_occupancy"]
        # 4 same-shape single-chunk requests inside a 200 ms window: at
        # least one launch must have carried more than one request.
        assert occ["max"] > 1
        assert rep["stages"]["encode.queue_wait"]["count"] == 4
        assert rep["counters"]["encode.device_launches"] >= 1
        # Launches are attributed to their real pool device: a
        # one-device pool books everything against device 0, and the
        # per-device split always sums to the total.
        assert (rep["counters"]["encode.device_launches.d0"]
                == rep["counters"]["encode.device_launches"])
        per_dev = sum(v for k, v in rep["counters"].items()
                      if k.startswith("encode.device_launches.d"))
        assert per_dev == rep["counters"]["encode.device_launches"]
        assert rep["counters"]["encode.batched_tiles"] == 4
        # The pool reporter is attached to the sink: occupancy gauge +
        # live queue depth appear in the same /metrics report.
        assert rep["sched"]["devices"] == 1
        assert "sched.device_occupancy.d0" in rep["sched"]
        assert rep["sched"]["device_queue_depth"] == 0
    finally:
        sched.close()


# --- failure isolation ------------------------------------------------

def test_failed_request_does_not_poison_shared_batch(sched):
    """A request that dispatches into a merged device batch and then
    dies must not corrupt the co-batched requests' output, nor wedge
    the scheduler for later requests."""
    imgs = _images(2, 64, seed=15)
    params = EncodeParams(lossless=True, levels=3, mct="on")
    serial = [encoder.encode_jp2(im, 8, params) for im in imgs]
    plan = make_plan(64, 64, 3, 3, True, 8, params.base_delta,
                     use_mct=True)
    bad_tiles = _images(1, 64, seed=99)[0][None]       # (1, 64, 64, 3)
    barrier = threading.Barrier(3)
    outs = [None, None]
    bad_err = []

    def good(i):
        barrier.wait()
        outs[i] = sched.encode_jp2(imgs[i], 8, params)

    def bad_request():
        svc = encoder.current_services()
        barrier.wait()
        svc.dispatch(plan, bad_tiles)     # joins the batch
        raise RuntimeError("client went away")

    def bad():
        try:
            sched.submit(bad_request)
        except RuntimeError as exc:
            bad_err.append(str(exc))

    threads = [threading.Thread(target=good, args=(0,)),
               threading.Thread(target=good, args=(1,)),
               threading.Thread(target=bad)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert bad_err == ["client went away"]
    assert outs == serial
    # The scheduler is still healthy afterwards.
    assert sched.encode_jp2(imgs[0], 8, params) == serial[0]
    assert sched.stats()["admitted"] == 0


def test_failed_device_launch_propagates_to_all_requests(sched):
    """If the merged launch itself dies, every co-batched waiter gets
    the error instead of hanging."""
    def boom():
        svc = encoder.current_services()
        with pytest.raises(ValueError):
            svc.dispatch(object(), np.zeros((1, 8, 8, 3), np.uint8))

    def fake_dispatch(plan, tiles, mode="rows", device=None):
        raise ValueError("bad launch")

    import bucketeer_tpu.codec.frontend as frontend
    orig = frontend.dispatch_frontend
    frontend.dispatch_frontend = fake_dispatch
    try:
        sched.submit(boom)
    finally:
        frontend.dispatch_frontend = orig


# --- admission control, priority, deadlines ---------------------------

def _hold_slot(sched, release: threading.Event,
               holding: threading.Event):
    def blocker():
        holding.set()
        release.wait(timeout=10)

    t = threading.Thread(target=lambda: sched.submit(blocker))
    t.start()
    holding.wait(timeout=5)
    return t


def test_admission_queue_full_raises(sched):
    tight = EncodeScheduler(queue_depth=1, max_concurrent=1,
                            pool_size=1, window_s=0)
    sink = Metrics()
    tight.set_metrics_sink(sink)
    release, holding = threading.Event(), threading.Event()
    t = _hold_slot(tight, release, holding)
    try:
        with pytest.raises(QueueFull) as exc_info:
            tight.submit(lambda: None)
        assert exc_info.value.retry_after > 0
        assert sink.report()["counters"]["encode.admission_rejects"] == 1
    finally:
        release.set()
        t.join()
        tight.close()


def test_single_image_priority_beats_batch(sched):
    tight = EncodeScheduler(queue_depth=8, max_concurrent=1,
                            pool_size=1, window_s=0)
    release, holding = threading.Event(), threading.Event()
    blocker = _hold_slot(tight, release, holding)
    order = []

    def worker(tag, priority):
        tight.submit(lambda: order.append(tag), priority=priority)

    try:
        tb = threading.Thread(target=worker, args=("batch",
                                                   PRIORITY_BATCH))
        tb.start()
        while tight.stats()["waiting"] < 1:
            time.sleep(0.005)
        ts = threading.Thread(target=worker, args=("single",
                                                   PRIORITY_SINGLE))
        ts.start()
        while tight.stats()["waiting"] < 2:
            time.sleep(0.005)
        release.set()
        blocker.join()
        tb.join()
        ts.join()
        # The later-arriving single-image request jumped the batch item.
        assert order == ["single", "batch"]
    finally:
        release.set()
        tight.close()


def test_deadline_expires_while_queued(sched):
    tight = EncodeScheduler(queue_depth=8, max_concurrent=1,
                            pool_size=1, window_s=0)
    release, holding = threading.Event(), threading.Event()
    blocker = _hold_slot(tight, release, holding)
    try:
        t0 = time.monotonic()
        with pytest.raises(DeadlineExceeded):
            tight.submit(lambda: None, deadline_s=0.1)
        assert time.monotonic() - t0 < 5
    finally:
        release.set()
        blocker.join()
        tight.close()


def test_deadline_checked_mid_pipeline():
    """The encoder polls the deadline at chunk-dispatch boundaries, so
    an expired request stops instead of finishing arbitrarily late."""
    sched = EncodeScheduler(queue_depth=4, max_concurrent=1,
                            pool_size=1, window_s=0)

    def slow_encode():
        svc = encoder.current_services()
        time.sleep(0.15)
        svc.check()

    try:
        with pytest.raises(DeadlineExceeded):
            sched.submit(slow_encode, deadline_s=0.05)
    finally:
        sched.close()


def test_get_scheduler_is_process_wide_singleton():
    assert get_scheduler() is get_scheduler()


def test_queue_full_message_carries_retry_after():
    exc = QueueFull(4, 2.0)
    assert exc.retry_after == 2.0
    assert "retry after" in str(exc)
