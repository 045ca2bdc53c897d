"""The comparison that decides ``correct`` on the four-chip pool cell,
``rgb8_lossy_pool4.csv_ingest``, as ``test_control.py`` checks it on the
one-chip cells: on the CPU with four host devices, at a size a test run
holds, the cell's traffic is driven through the served system with the
chip check skipped, and

- a sound run is correct;
- the control (the reference in the program's place, one precision
  down) is not;
- a run with the timed path broken underneath is not, once for each
  ingest fault of ``test_control.py`` and once for a fault of the pool
  itself: every launch on a device other than the pool's first has its
  tiles altered. The chips exchange nothing, but they share out the
  work, and a pool that handed its other devices different work would
  show so.

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests/test_pool_control.py -q

Takes a few minutes: every case starts a server of its own.
"""
from __future__ import annotations

import os
import sys
import textwrap

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

import limits  # noqa: E402
import run  # noqa: E402
from test_control import FAULTS, SECONDS, SEED  # noqa: E402

CELL = "rgb8_lossy_pool4.csv_ingest"
# Every item of a small window checked: a sample of two can fall on
# the pool's first device alone.
SMALL = {"height": 512, "width": 512, "pool": 2,
         "items_per_job": 2, "check_items": 64}
HOST_DEVICES = "--xla_force_host_platform_device_count=4"

POOL_FAULTS = {
    **FAULTS,
    # Every launch on a pool device other than the first has a quarter
    # of its samples moved by 12 levels.
    "pool_off_first_altered": """
        import jax
        import numpy as np
        from bucketeer_tpu.codec import frontend
        dispatch = frontend.dispatch_frontend
        def altered(plan, tiles, *args, device=None, **kwargs):
            if device is not None and device != jax.devices()[0]:
                tiles = tiles.copy()
                h, w = tiles.shape[1:3]
                top = tiles[:, :h // 2, :w // 2].astype(np.int64) + 12
                tiles[:, :h // 2, :w // 2] = np.clip(top, 0, 255)
            return dispatch(plan, tiles, *args, device=device, **kwargs)
        frontend.dispatch_frontend = altered
    """,
}


@pytest.fixture(autouse=True)
def four_host_devices(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS", HOST_DEVICES)


def test_sound_pool_run_is_correct_and_control_is_not():
    rows = limits.measure(CELL, [SEED], SECONDS, require_tpu=False,
                          overrides=SMALL)
    config = run.cell_plan(run.ROOT, CELL)["config"]
    program, control = rows[0]["program"], rows[0]["control"]
    assert rows[0]["failed"] == 0 and program["checked"] > 0
    assert run.judge(program, config)[0], program
    assert not run.judge(control, config)[0], control


@pytest.mark.parametrize("fault", ["ingest_altered", "ingest_half",
                                   "ingest_dropped",
                                   "pool_off_first_altered"])
def test_broken_pool_path_is_not_correct(fault, tmp_path):
    plant = str(tmp_path / f"{fault}.py")
    with open(plant, "w", encoding="utf-8") as fh:
        fh.write(textwrap.dedent(POOL_FAULTS[fault]))
    result, _lines = run.run_cell(
        CELL, SEED, SECONDS, False, require_tpu=False, overrides=SMALL,
        plant=plant, peaks={"hbm_bytes_per_s": 819e9})
    assert result["correct"] is False, result["checks"]
