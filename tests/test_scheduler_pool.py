"""Device-pool data plane (engine/scheduler.py, ISSUE 17): the
multi-device scheduler must spread launches across pool workers with
exact per-device attribution, stay byte-identical to the serialized
single-device path for every job kind (encode / decode / tensor), size
itself from config/env, and keep admission control intact with N
workers. Runs on the conftest-forced 8-device CPU mesh."""
import threading
import time

import numpy as np
import pytest

from bucketeer_tpu.codec import encoder
from bucketeer_tpu.codec.decode.decoder import decode
from bucketeer_tpu.codec.encoder import EncodeParams
from bucketeer_tpu.engine.scheduler import (DeadlineExceeded,
                                            EncodeScheduler, QueueFull)
from bucketeer_tpu.server.metrics import Metrics
from bucketeer_tpu.tensor import decode_tensor, encode_tensor

JOIN_S = 10


def _images(n, size, seed, bitdepth=8):
    rng = np.random.default_rng(seed)
    dtype = np.uint8 if bitdepth == 8 else np.uint16
    return [rng.integers(0, 1 << bitdepth, (size, size, 3), dtype=dtype)
            for _ in range(n)]


def _run_concurrent(fns):
    """Run the thunks on a shared barrier; return (results, errors)."""
    outs = [None] * len(fns)
    errs = [None] * len(fns)
    barrier = threading.Barrier(len(fns))

    def client(i):
        barrier.wait()
        try:
            outs[i] = fns[i]()
        except BaseException as exc:          # surfaced to the test
            errs[i] = exc

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(fns))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive(), "pool client hung"
    return outs, errs


def _per_device(counters, family):
    return {k: v for k, v in counters.items()
            if k.startswith(f"{family}.device_launches.d")}


# --- launch distribution and attribution ------------------------------

def test_concurrent_launches_spread_over_distinct_devices():
    """Two overlapping incompatible launches land on two distinct pool
    workers (the gate makes the overlap deterministic: the first launch
    cannot finish until the second has started), and the per-device
    counters attribute each to its real worker."""
    ev = [threading.Event(), threading.Event()]
    seen = []
    lock = threading.Lock()

    def gated_launch(plan, tiles, mode="rows"):
        with lock:
            i = len(seen)
            seen.append(plan)
        ev[i].set()
        assert ev[1 - i].wait(timeout=JOIN_S), "peer launch never ran"
        return ("pending", plan)

    sched = EncodeScheduler(queue_depth=16, max_concurrent=4,
                            pool_size=2, window_s=0, devices=4)
    sched.launch_fn = gated_launch
    sink = Metrics()
    sched.set_metrics_sink(sink)
    try:
        outs, errs = _run_concurrent([
            lambda: sched.dispatch_frontend(
                ("p1",), np.zeros((1, 2, 2, 3), np.uint8)),
            lambda: sched.dispatch_frontend(
                ("p2",), np.zeros((1, 2, 2, 3), np.uint8))])
        assert errs == [None, None]
        assert sorted(o[1][0] for o in outs) == ["p1", "p2"]
        counters = sink.report()["counters"]
        per_dev = _per_device(counters, "encode")
        assert counters["encode.device_launches"] == 2
        assert len(per_dev) >= 2, per_dev       # >= 2 distinct devices
        assert sum(per_dev.values()) == 2
        rep = sched.pool_report()
        assert rep["devices"] == 4
        assert rep["device_queue_depth"] == 0
    finally:
        sched.close()


# --- byte-identity matrix on the 8-device mesh ------------------------

@pytest.fixture
def sched():
    # An explicit 2-device pool: conftest defaults the suite to one
    # device (each engaged device pays its own frontend recompile on
    # the CPU probe), so multi-device byte-identity opts in with the
    # smallest real pool.
    s = EncodeScheduler(queue_depth=16, max_concurrent=4, pool_size=2,
                        window_s=0.2, devices=2)
    yield s
    s.close()


def test_broken_backend_raises_instead_of_deviceless_worker(monkeypatch):
    """A backend that fails to come up surfaces at the pool: no
    deviceless worker hides it behind default placement."""
    import jax

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", broken)
    s = EncodeScheduler(queue_depth=4, max_concurrent=1)
    try:
        with pytest.raises(RuntimeError, match="Unable to initialize"):
            s._ensure_workers()
        assert not s.device_threads_alive()
    finally:
        s.close()


_LOSSLESS = {"lossless": True, "levels": 3}
_RATE = {"lossless": False, "levels": 3, "base_delta": 2.0, "rate": 1.5}


@pytest.mark.parametrize("bitdepth,kw", [(8, _LOSSLESS), (8, _RATE),
                                         (16, _LOSSLESS)],
                         ids=["lossless8", "rate8", "lossless16"])
def test_pool_encode_bytes_identical(sched, bitdepth, kw):
    imgs = _images(4, 64, seed=21, bitdepth=bitdepth)
    params = EncodeParams(**kw)
    serial = [encoder.encode_jp2(im, bitdepth, params) for im in imgs]
    outs, errs = _run_concurrent(
        [lambda im=im: sched.encode_jp2(im, bitdepth, params)
         for im in imgs])
    assert errs == [None] * 4
    assert outs == serial


def test_pool_decode_bytes_identical(sched):
    imgs = _images(3, 64, seed=22)
    params = EncodeParams(lossless=True, levels=2)
    blobs = [encoder.encode_jp2(im, 8, params) for im in imgs]
    serial = [decode(b) for b in blobs]
    outs, errs = _run_concurrent(
        [lambda b=b: sched.read(decode, b) for b in blobs])
    assert errs == [None] * 3
    for got, want in zip(outs, serial):
        assert np.array_equal(got, want)


@pytest.mark.slow
def test_pool_tensor_bytes_identical():
    # Slow-marked: a two-device pool compiles the device MQ chunk
    # program once per assigned device (~1 min each on the CPU probe);
    # the CI multichip job runs this file unfiltered. Small-magnitude
    # int8 keeps the sequential scans affordable (same trick as
    # test_tensor_codec).
    sched = EncodeScheduler(queue_depth=16, max_concurrent=4,
                            pool_size=2, window_s=0.2, devices=2)
    rng = np.random.default_rng(23)
    arrs = [rng.integers(-3, 4, size=(600,), dtype=np.int8)
            for _ in range(3)]
    try:
        serial = [encode_tensor(x, device="device") for x in arrs]
        outs, errs = _run_concurrent(
            [lambda x=x: sched.submit_tensor(encode_tensor, x,
                                             device="device")
             for x in arrs])
        assert errs == [None] * 3
        assert outs == serial
        for blob, x in zip(outs, arrs):
            assert np.array_equal(decode_tensor(blob), x)
    finally:
        sched.close()


# --- pool sizing and config ------------------------------------------

def test_devices_env_and_ctor_sizing(monkeypatch):
    monkeypatch.setenv("BUCKETEER_SCHED_DEVICES", "3")
    sched = EncodeScheduler()
    sched.launch_fn = lambda plan, tiles, mode="rows": "pending"
    try:
        assert sched.devices == 3
        sched.dispatch_frontend(("p",), np.zeros((1, 2, 2, 3), np.uint8))
        assert sched.pool_report()["devices"] == 3
    finally:
        sched.close()
    explicit = EncodeScheduler(devices=2)
    try:
        assert explicit.devices == 2      # ctor beats env
    finally:
        explicit.close()


def test_devices_cap_clamps_to_available():
    sched = EncodeScheduler(devices=64)
    try:
        with sched._dq_cv:
            sched._ensure_devices_locked()
            assert len(sched._devices) == 8   # the forced host mesh
    finally:
        sched.close()


def test_configure_devices():
    sched = EncodeScheduler()
    try:
        sched.configure(devices=2)
        assert sched.devices == 2
    finally:
        sched.close()


# --- admission control with N workers ---------------------------------

def test_queue_full_and_deadline_with_pool_workers():
    """Admission stays bounded however many pool workers exist: with
    both slots held, a queued deadline expires typed
    (DeadlineExceeded) and the full queue rejects typed (QueueFull)."""
    sched = EncodeScheduler(queue_depth=3, max_concurrent=2,
                            pool_size=2, window_s=0, devices=4)
    sched.launch_fn = lambda plan, tiles, mode="rows": "pending"
    release = threading.Event()
    holding = [threading.Event(), threading.Event()]

    def hold(i):
        def body():
            holding[i].set()
            release.wait(timeout=JOIN_S)
        sched.submit(body)

    threads = [threading.Thread(target=hold, args=(i,))
               for i in range(2)]
    for t in threads:
        t.start()
    try:
        for h in holding:
            assert h.wait(timeout=JOIN_S)
        # Both slots busy, one admission slot free: a queued request's
        # deadline expires typed while it waits for a slot.
        t0 = time.monotonic()
        with pytest.raises(DeadlineExceeded):
            sched.submit(lambda: None, deadline_s=0.05)
        assert time.monotonic() - t0 < JOIN_S
        # Fill the last admission slot with a patient queued request,
        # then the next arrival bounces typed with a retry hint.
        queued = threading.Thread(target=lambda: sched.submit(lambda: None))
        queued.start()
        threads.append(queued)
        while sched.stats()["waiting"] < 1:
            time.sleep(0.005)
        with pytest.raises(QueueFull) as exc_info:
            sched.submit(lambda: None)
        assert exc_info.value.retry_after > 0
    finally:
        release.set()
        for t in threads:
            t.join(timeout=JOIN_S)
            assert not t.is_alive()
        sched.close()


# --- tensor merge ------------------------------------------------------

def test_tensor_merge_stub_occupancy_and_slicing():
    """Deterministic fast twin of the byte-identity test below: while
    the lone worker is held inside a gated launch, two same-key tensor
    chunks queue behind it and merge into ONE launch
    (tensor.batch_occupancy == 2), each waiter getting its own
    (result, offset, n_blocks) slice of the merged result."""
    sched = EncodeScheduler(queue_depth=16, max_concurrent=4,
                            pool_size=2, window_s=0, devices=1)
    gate = threading.Event()
    started = threading.Event()
    launches: list = []

    def stub_launch(plan, rows, mode="rows"):
        if mode == "rows":                        # the holder job
            started.set()
            assert gate.wait(timeout=JOIN_S), "gate never released"
            return "pending"
        launches.append(np.asarray(rows).shape[0])
        return ("merged", len(rows))

    sched.launch_fn = stub_launch
    sink = Metrics()
    sched.set_metrics_sink(sink)
    outs = [None, None]
    threads = []
    try:
        holder = threading.Thread(
            target=lambda: sched.dispatch_frontend(
                ("hold",), np.zeros((1, 2, 2, 3), np.uint8)))
        holder.start()
        threads.append(holder)
        assert started.wait(timeout=JOIN_S)
        rows = np.zeros((2, 8), np.float32)
        floors = np.zeros(2, np.int32)
        for i in range(2):
            t = threading.Thread(
                target=lambda i=i: outs.__setitem__(
                    i, sched.dispatch_tensor_chunk(rows, floors)))
            t.start()
            threads.append(t)
        while sched.stats()["device_queue_depth"] < 2:
            time.sleep(0.005)
        gate.set()
        for t in threads:
            t.join(timeout=JOIN_S)
            assert not t.is_alive(), "merge client hung"
        # One merged launch of both jobs' rows; disjoint block slices
        # of the one shared result.
        assert launches == [4]
        assert sorted(o[1] for o in outs) == [0, 2]
        assert all(o[0] == ("merged", 4) and o[2] == 2 for o in outs)
        rep = sink.report()
        assert rep["values"]["tensor.batch_occupancy"]["max"] == 2
        counters = rep["counters"]
        assert counters["tensor.device_launches"] == 1
        assert counters["tensor.device_launches.d0"] == 1
    finally:
        gate.set()
        sched.close()


@pytest.mark.slow
def test_tensor_merge_byte_identity_and_occupancy():
    """Two concurrent same-dtype tensor jobs on a one-worker pool merge
    into shared device launches (tensor.batch_occupancy > 1) and stay
    byte-identical to serial encodes — the merged launch's per-job
    block slices never leak across jobs. Slow-marked: the merged
    2-job chunk shape compiles its own device MQ program (~1 min on
    the CPU probe); the CI multichip job runs this file unfiltered."""
    sched = EncodeScheduler(queue_depth=16, max_concurrent=4,
                            pool_size=2, window_s=0.2, devices=1)
    sink = Metrics()
    sched.set_metrics_sink(sink)
    rng = np.random.default_rng(26)
    arrs = [rng.integers(-3, 4, size=(600,), dtype=np.int8),
            rng.integers(-3, 4, size=(600,), dtype=np.int8)]
    try:
        serial = [encode_tensor(x, device="device") for x in arrs]
        outs, errs = _run_concurrent(
            [lambda x=x: sched.submit_tensor(encode_tensor, x,
                                             device="device")
             for x in arrs])
        assert errs == [None, None]
        assert outs == serial
        rep = sink.report()
        occ = rep["values"]["tensor.batch_occupancy"]
        assert occ["max"] > 1, occ
        counters = rep["counters"]
        assert counters["tensor.device_launches.d0"] == \
            counters["tensor.device_launches"]
    finally:
        sched.close()


# --- graftrace regression ---------------------------------------------

@pytest.mark.parametrize("seed", [0, 1])
def test_graftrace_device_pool_storm_pinned_schedules(seed):
    """Pinned-schedule sweep of the device_pool_storm scenario (fatal
    worker replacement, cross-worker priority order, close-drain over
    a 4-device pool). Deterministic per seed."""
    from bucketeer_tpu.analysis.graftrace import explore

    findings, summary = explore.run_race(
        "bucketeer_tpu", scenario_names=["device_pool_storm"],
        schedules=24, seed=seed, budget_s=240)
    assert findings == [], "\n".join(f.render() for f in findings)
    assert summary["deadlocks"] == 0
    assert summary["invariant_failures"] == 0
    assert summary["races"] == 0
