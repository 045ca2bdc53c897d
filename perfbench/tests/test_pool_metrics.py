"""Self-test of what the four-chip pool cell reads: the trace reduction
over four chips when one of them ran nothing, and the readers of
``pool_spread`` and ``t1_wait_ms_per_mpix``. No chip and no server
needed:

    python -m pytest perfbench/tests/test_pool_metrics.py -q
"""
from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import devtrace  # noqa: E402
import run  # noqa: E402

MS = 1_000_000


def test_a_chip_with_no_event_counts_as_idle_not_dropped():
    """Four chips, one of which ran nothing in the window (its plane is
    there with no event, or with empty lines): its busy time is 0 in
    the mean, and the chip stays in the count."""
    raw = {"devices": {
        "/device:TPU:0": {"XLA Ops": [["fusion.1", 0, 40 * MS]]},
        "/device:TPU:1": {"XLA Ops": [["fusion.1", 50 * MS, 20 * MS]]},
        "/device:TPU:2": {"XLA Modules": [["jit_gather(3)", 0, 20 * MS]]},
        "/device:TPU:3": {"XLA Ops": [], "XLA Modules": []}},
        "host": []}
    out = devtrace.reduce(raw, 100 * MS)
    assert sorted(out["busy_by_device"]) == [f"/device:TPU:{i}"
                                             for i in range(4)]
    assert out["busy_by_device"]["/device:TPU:3"] == 0.0
    assert out["busy_s"] == pytest.approx((0.040 + 0.020 + 0.020) / 4)
    raw["devices"]["/device:TPU:3"] = {}
    assert devtrace.reduce(raw, 100 * MS)["busy_s"] == pytest.approx(0.020)
    read = run.reader(ROOT, "device_idle.ingest")
    assert read({"trace": out}) == pytest.approx(80.0)


@pytest.mark.parametrize("tiles,want", [
    ((8, 8, 8, 8), 100.0),
    ((16, 8, 4, 4), 50.0),
    ((20, 12, 0, 0), 0.0),
])
def test_pool_spread_reads_the_least_used_device(tiles, want):
    read = run.reader(ROOT, "pool_spread")
    counters = {f"encode.batched_tiles.d{i}": n for i, n in enumerate(tiles)}
    counters["encode.batched_tiles"] = sum(tiles)
    counters["encode.device_launches.d0"] = 3
    assert read({"counters": counters}) == pytest.approx(want)


def test_pool_spread_reads_nothing_without_the_counters():
    read = run.reader(ROOT, "pool_spread")
    assert read({"counters": {"encode.batched_tiles": 16}}) is None
    assert read({"counters": {"encode.batched_tiles.d0": 0,
                              "encode.batched_tiles.d1": 0}}) is None


def test_t1_wait_reader_per_first_attempt_megapixel():
    read = run.reader(ROOT, "t1_wait_ms_per_mpix")
    stages = {"encode.t1_wait": {"count": 6, "sum_s": 0.3, "mpixels": 12.0}}
    assert read({"stages": stages}) == pytest.approx(25.0)
    assert read({"stages": {}}) is None
    stages["encode.t1_wait"]["mpixels"] = 0
    assert read({"stages": stages}) is None
