"""Benchmark harness: the BASELINE configs, end to end, on the TPU.

Measures the product encode path (device transform + Tier-1 entropy
coding + Tier-2/boxing) against the 500 MPix/s north star
(BASELINE.json) and prints exactly one JSON line:

- config 1: single 4096x4096 RGB -> lossy JP2 with the *real* reference
  recipe (``-rate 3``, 512x512 tiles, 6 levels, RPCL, 6 layers —
  KakaduConverter.java:38-44), not the easier untargeted config earlier
  rounds measured.
- config 2: batch of 2Kx2K RGB images, lossy 9/7, 5 levels.
- config 3: lossless RCT-free 5/3 on a 16-bit grayscale archival scan.
- config 4: sharded-DWT dryrun — the row-sharded multi-level transform
  (parallel/sharded_dwt.py) over the device mesh; reported as a dryrun
  number because Tier-1/Tier-2 are excluded.
- config 5: mixed-size batch with upload overlapped with encode (the
  S3BucketVerticle-overlap analog: a background writer drains finished
  encodes while the next image encodes).

The benchmark measures a TPU: on any other platform, or when any
config raises, it exits non-zero.

Env knobs: BENCH_SMOKE=1 shrinks every config to CI-smoke size;
BENCH_SIZE / BENCH_REPEATS / BENCH_BATCH_N / BENCH_BATCH_SIZE /
BENCH_SCAN_SIZE / BENCH_SHARD_SIZE / BENCH_CONFIGS (comma list, e.g.
"1,4") override individual configs.
"""
from __future__ import annotations

import json
import os
import platform as platform_mod
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

BASELINE_MPIX_S = 500.0
SMOKE = os.environ.get("BENCH_SMOKE", "") not in ("", "0")


def _env_int(name: str, default: int, smoke: int | None = None) -> int:
    if name in os.environ:
        return int(os.environ[name])
    return smoke if (SMOKE and smoke is not None) else default


# --- backend bring-up ----------------------------------------------------

def init_backend() -> dict:
    """Platform metadata of the TPU this benchmark measures; raises
    when JAX's backend is anything else."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise RuntimeError(
            f"bench.py measures a TPU; JAX's backend is "
            f"{devices[0].platform!r}")
    return {"platform": devices[0].platform, "n_devices": len(devices)}


# --- synthetic content ---------------------------------------------------

def synthetic_photo(h: int, w: int | None = None,
                    seed: int = 7) -> np.ndarray:
    """Photograph-like content: smooth gradients + texture + edges, so the
    entropy coder sees realistic significance statistics."""
    w = w or h
    rng = np.random.default_rng(seed)
    y, x = np.mgrid[0:h, 0:w]
    base = (128 + 96 * np.sin(2 * np.pi * x / w * 3)
            * np.cos(2 * np.pi * y / h * 2))
    texture = rng.normal(0, 12, size=(h, w))
    edges = ((x // 256 + y // 256) % 2) * 20
    img = np.stack([
        np.clip(base + texture + edges, 0, 255),
        np.clip(base * 0.8 + texture + 30, 0, 255),
        np.clip(base * 0.6 + texture + edges + 60, 0, 255),
    ], axis=-1)
    return img.astype(np.uint8)


def synthetic_scan16(size: int, seed: int = 11) -> np.ndarray:
    """16-bit grayscale archival-scan-like content (BASELINE config 3)."""
    rng = np.random.default_rng(seed)
    y, x = np.mgrid[0:size, 0:size]
    base = 32768 + 18000 * np.sin(x / 37.0) * np.cos(y / 29.0)
    grain = rng.normal(0, 600, size=(size, size))
    return np.clip(base + grain, 0, 65535).astype(np.uint16)


def _timed(fn, repeats: int) -> tuple:
    """(best seconds, last result) over ``repeats`` runs after the
    caller's warmup."""
    best, result = float("inf"), None
    for _ in range(repeats):
        t0 = time.perf_counter()
        result = fn()
        best = min(best, time.perf_counter() - t0)
    return best, result


def _stage_profile(sink, prefixes=("encode.", "decode.")) -> dict:
    """Per-stage split from a Metrics sink, for the bench JSON: the
    same stage registry /metrics serves (front-end dispatch vs CX/D vs
    MQ replay vs Tier-2, decode segments), no parallel timer set to
    drift out of sync."""
    out = {}
    for name, st in sink.report()["stages"].items():
        if not name.startswith(tuple(prefixes)):
            continue
        entry = {"total_s": st["total_s"], "count": st["count"]}
        for k in ("mpixels_per_s", "items_per_s", "items"):
            if k in st:
                entry[k] = st[k]
        out[name] = entry
    return out


def _stage_percentiles(sink, prefixes=("encode.", "decode.")) -> dict:
    """Server-side p50/p95/p99 per stage from the sink's log2-bucket
    histograms (ISSUE 14) — the bench JSON twin of ``stage_profile``,
    so tail behavior ships next to the throughput split."""
    out = {}
    for name, st in sink.report()["stages"].items():
        if not name.startswith(tuple(prefixes)):
            continue
        if "p95_ms" not in st:
            continue
        out[name] = {k: st[k] for k in ("p50_ms", "p95_ms", "p99_ms")}
    return out


def _assert_p95_agreement(server_ms, client_ms, context: str) -> None:
    """Server-side histograms must agree with client-observed
    percentiles: quarter-octave buckets bound quantization at ~19%,
    the rest of the window covers sampling noise on smoke-sized runs
    plus the client's extra thread-scheduling overhead."""
    assert server_ms is not None, f"{context}: no server-side histogram"
    assert abs(server_ms - client_ms) <= 0.5 * client_ms + 10.0, (
        f"{context}: server-side p95 {server_ms:.1f} ms disagrees with "
        f"client-side {client_ms:.1f} ms beyond tolerance")


# --- configs -------------------------------------------------------------

# The three Tier-1 modes the split compares: legacy host Tier-1 over
# packed bitmaps, device CX/D + host MQ replay, and full-device Tier-1
# (CX/D + MQ coder on device, host = block assembly only).
_SPLIT_MODES = (("legacy", dict(device_cxd=False, device_mq=False)),
                ("cxd", dict(device_cxd=True, device_mq=False)),
                ("device_mq", dict(device_mq=True)))


def _tier1_split_report(img, params) -> dict:
    """Host-coding segment across the three Tier-1 modes (legacy /
    MQ-replay / device-MQ): one instrumented encode per mode, reporting
    the host seconds, the device Tier-1 segments, symbol and byte
    throughput and the measured overlap ratio — plus re-timed
    host-Tier-1-only numbers, whose ratios are the acceptance gates
    (ISSUE 3: replay vs legacy; ISSUE 9: device-MQ host work <= 1/5 of
    replay's — with device MQ the host's whole Tier-1 share is
    assemble_mq_blocks)."""
    import dataclasses

    from bucketeer_tpu.codec import cxd as cxd_mod
    from bucketeer_tpu.codec import encoder, t1_batch
    from bucketeer_tpu.server.metrics import Metrics

    # Two probes. Serial (the config's own tiling, usually one chunk):
    # the host segment runs uncontended, so the per-mode host seconds
    # compare cleanly. Overlap (many single-tile chunks): the ratio the
    # pipeline actually achieves when host coding hides behind device
    # compute — on CPU the two sides share cores, which would skew the
    # serial timing if merged into one probe.
    out: dict = {}
    calls: dict = {}
    for mode, flags in _SPLIT_MODES:
        calls[mode] = []
        out[mode] = _tier1_split_one(
            encoder, Metrics, img,
            dataclasses.replace(params, **flags), mode,
            capture=calls[mode])
    # The sink segments above include scheduling noise at smoke sizes;
    # the speedup numbers re-time the captured host Tier-1 calls alone
    # (same inputs the measured encode used), min of 3 — this is "host
    # Tier-1 time per chunk" with nothing else on the cores.
    for mode, fn in (("legacy", t1_batch.encode_packed),
                     ("cxd", t1_batch.encode_cxd),
                     ("device_mq", cxd_mod.assemble_mq_blocks)):
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for args in calls[mode]:
                fn(*args)
            best = min(best, time.perf_counter() - t0)
        out[mode]["host_tier1_retimed_s"] = round(best, 4)
    legacy_s = out["legacy"]["host_tier1_retimed_s"]
    cxd_s = out["cxd"]["host_tier1_retimed_s"]
    mq_s = out["device_mq"]["host_tier1_retimed_s"]
    out["host_tier1_speedup"] = (round(legacy_s / cxd_s, 2)
                                 if cxd_s > 0 else None)
    # The ISSUE 9 acceptance number: host Tier-1 work with the MQ coder
    # on device vs the MQ-replay mode's host share.
    out["host_reduction_device_mq_vs_replay"] = (
        round(cxd_s / mq_s, 2) if mq_s > 0 else None)

    side = min(128, img.shape[0], img.shape[1])
    ov_img = img[:side, :side]
    ov_params = dataclasses.replace(params, tile_size=min(64, side))
    prev_tiles = os.environ.get("BUCKETEER_OVERLAP_TILES")
    os.environ["BUCKETEER_OVERLAP_TILES"] = "1"
    try:
        # Overlap is a device-vs-host race; in device-MQ mode the host
        # side is assembly-only (nothing to hide), so the probe covers
        # the two modes with a real host segment.
        out["overlap_probe"] = {
            mode: _tier1_split_one(
                encoder, Metrics, ov_img,
                dataclasses.replace(ov_params, **flags),
                mode)["overlap_ratio"]
            for mode, flags in _SPLIT_MODES[:2]}
    finally:
        if prev_tiles is None:
            os.environ.pop("BUCKETEER_OVERLAP_TILES", None)
        else:
            os.environ["BUCKETEER_OVERLAP_TILES"] = prev_tiles
    out["graftcost_prediction"] = _graftcost_prediction(out)
    return out


def _graftcost_prediction(split: dict) -> dict:
    """The static cost model's device-Tier-1 symbol throughput per
    machine model (graftcost.tier1_prediction) beside the measured
    device-MQ number, with the prediction error on the
    backend-matching model — every bench run calibrates the model, so
    its machine numbers are tracked against reality instead of
    trusted."""
    import jax

    from bucketeer_tpu.analysis import graftcost

    modeled = graftcost.tier1_prediction()
    if not modeled:
        return {}
    entry: dict = {"modeled": modeled}
    measured = (split.get("device_mq") or {}).get("symbols_per_s") or 0
    entry["measured_symbols_per_s"] = measured
    machine = graftcost.machine_for_device_kind(
        jax.devices()[0].device_kind).name
    entry["machine_for_error"] = machine
    mp = modeled.get(machine, {}).get("symbols_per_s")
    if measured and mp:
        # Signed relative error: +1.0 means the model promised double
        # what the hardware delivered.
        entry["prediction_error"] = round(mp / measured - 1.0, 3)
    return entry


def _tier1_split_one(encoder, Metrics, img, p, mode,
                     capture: list | None = None) -> dict:
    from bucketeer_tpu.codec import cxd as cxd_mod
    from bucketeer_tpu.codec import t1_batch

    encoder.encode_jp2(img, 8, p)               # warm: exclude compiles
    sink = Metrics()
    encoder.set_metrics_sink(sink)
    orig = (t1_batch.encode_packed, t1_batch.encode_cxd,
            cxd_mod.assemble_mq_blocks)
    if capture is not None:
        # Record the host Tier-1 inputs so the caller can re-time the
        # host calls in isolation after the encode. In device-MQ mode
        # the host's whole Tier-1 share is the block assembly.
        def cap_packed(*args, **kwargs):
            capture.append(args)
            return orig[0](*args, **kwargs)

        def cap_cxd(streams, **kwargs):
            capture.append((streams,))
            return orig[1](streams, **kwargs)

        def cap_mq(*args):
            capture.append(args)
            return orig[2](*args)

        t1_batch.encode_packed = cap_packed
        t1_batch.encode_cxd = cap_cxd
        cxd_mod.assemble_mq_blocks = cap_mq
    try:
        encoder.encode_jp2(img, 8, p)
    finally:
        encoder.set_metrics_sink(None)
        (t1_batch.encode_packed, t1_batch.encode_cxd,
         cxd_mod.assemble_mq_blocks) = orig
    rep = sink.report()
    st = rep["stages"]
    ov = rep.get("overlap", {}).get("encode", {})
    entry = {
        "host_tier1_s": st["encode.host_code"]["total_s"],
        "device_s": st["encode.device_dispatch"]["total_s"],
        "overlap_ratio": ov.get("overlap_ratio", 0.0),
    }
    if mode == "cxd":
        entry["mq_replay_s"] = st["encode.mq_replay"]["total_s"]
        entry["cxd_device_s"] = st["encode.cxd_device"]["total_s"]
        entry["symbols"] = st["encode.mq_replay"].get("items", 0)
        entry["symbols_per_s"] = st["encode.mq_replay"].get(
            "items_per_s", 0)
    elif mode == "device_mq":
        entry["cxd_device_s"] = st["encode.cxd_device"]["total_s"]
        entry["mq_device_s"] = st["encode.mq_device"]["total_s"]
        entry["t1_device_total_s"] = st[
            "encode.t1_device_total"]["total_s"]
        entry["symbols"] = st["encode.t1_device_total"].get("items", 0)
        entry["symbols_per_s"] = st["encode.t1_device_total"].get(
            "items_per_s", 0)
        entry["bytes"] = st["encode.mq_device"].get("items", 0)
        entry["bytes_per_s"] = st["encode.mq_device"].get(
            "items_per_s", 0)
    return entry


def _want_tier1_split() -> bool:
    """The CX/D comparison runs the jnp scan as the 'device' on CPU —
    fine at smoke sizes, prohibitive at the full 4096². Auto: smoke or
    a real accelerator; BENCH_CXD=1/0 forces."""
    import jax

    from bucketeer_tpu.config import truthy

    env = os.environ.get("BENCH_CXD", "auto")
    if env != "auto":
        return truthy(env)
    return SMOKE or jax.default_backend() != "cpu"


def config1_single_4k(repeats: int) -> dict:
    """BASELINE config 1, real recipe: 4096x4096 RGB -> lossy `-rate 3`,
    512 tiles, 6 levels, RPCL, 6 layers, SOP/EPH/PLT."""
    from bucketeer_tpu.codec import encoder
    from bucketeer_tpu.codec.encoder import EncodeParams

    from bucketeer_tpu.server.metrics import Metrics

    size = _env_int("BENCH_SIZE", 4096, smoke=512)
    img = synthetic_photo(size)
    params = EncodeParams.kakadu_recipe(lossless=False, rate=3.0)
    # Warm with the real geometry: a smaller slab would dispatch
    # different chunk/batch-bucket program variants and leave XLA
    # compiles inside the first timed repeat.
    encoder.encode_jp2(img, 8, params)
    # Per-stage split of the timed repeats via the /metrics stage
    # registry (ROADMAP item 5: where does the wall clock actually go).
    sink = Metrics()
    encoder.set_metrics_sink(sink)
    try:
        best, data = _timed(lambda: encoder.encode_jp2(img, 8, params),
                            repeats)
    finally:
        encoder.set_metrics_sink(None)
    mpix = size * size / 1e6
    result = {"value": round(mpix / best, 3), "unit": "MPix/s",
              "seconds": round(best, 3),
              "image": f"{size}x{size}x3 uint8",
              "recipe": "kakadu rate=3 tiles=512 levels=6",
              "output_bytes": len(data),
              "bpp": round(8.0 * len(data) / (size * size), 3),
              "stage_profile": _stage_profile(sink),
              "repeats": repeats}
    if _want_tier1_split():
        # On CPU, bound the jnp-scan 'device' cost: the host-segment
        # comparison is per-chunk anyway, so a 192² slab is
        # representative and keeps smoke CI fast (the three-mode split
        # runs the CX/D and MQ scans several times each).
        import jax

        split_img = (img if jax.default_backend() != "cpu"
                     else img[:min(size, 192), :min(size, 192)])
        result["tier1_split"] = _tier1_split_report(split_img, params)
    # Pow-2 bucket occupancy of everything this config launched,
    # weighted by the recorded workload-shape histogram (the graftcost
    # seams in frontend/cxd/decode record each launch).
    from bucketeer_tpu.analysis import graftcost

    hist = graftcost.bucket_histogram()
    if hist:
        result["padding_waste"] = graftcost.padding_waste(hist)
    return result


def config2_batch_2k(repeats: int) -> dict:
    """BASELINE config 2 (scaled by env): N 2Kx2K RGB images, lossy
    CDF 9/7, 5 DWT levels, aggregate throughput."""
    from bucketeer_tpu.codec import encoder
    from bucketeer_tpu.codec.encoder import EncodeParams

    n = _env_int("BENCH_BATCH_N", 8, smoke=2)
    size = _env_int("BENCH_BATCH_SIZE", 2048, smoke=256)
    imgs = [synthetic_photo(size, seed=100 + i) for i in range(n)]
    params = EncodeParams(lossless=False, levels=5, tile_size=1024,
                          base_delta=2.0, rate=3.0)
    encoder.encode_jp2(imgs[0], 8, params)                 # compile

    def run():
        return sum(len(encoder.encode_jp2(im, 8, params)) for im in imgs)

    best, total_bytes = _timed(run, repeats)
    mpix = n * size * size / 1e6
    return {"value": round(mpix / best, 3), "unit": "MPix/s",
            "seconds": round(best, 3), "images": n,
            "image": f"{size}x{size}x3 uint8",
            "output_bytes": total_bytes, "repeats": repeats}


def config3_lossless16(repeats: int) -> dict:
    """BASELINE config 3: lossless 5/3 on a 16-bit grayscale scan."""
    from bucketeer_tpu.codec import encoder
    from bucketeer_tpu.codec.encoder import EncodeParams

    size = _env_int("BENCH_SCAN_SIZE", 2048, smoke=256)
    img = synthetic_scan16(size)
    params = EncodeParams(lossless=True, levels=5,
                          tile_size=min(1024, size))
    encoder.encode_jp2(img, 16, params)    # warm the real geometry
    best, data = _timed(lambda: encoder.encode_jp2(img, 16, params),
                        repeats)
    mpix = size * size / 1e6
    return {"value": round(mpix / best, 3), "unit": "MPix/s",
            "seconds": round(best, 3),
            "image": f"{size}x{size} uint16",
            "output_bytes": len(data),
            "bpp": round(8.0 * len(data) / (size * size), 3),
            "repeats": repeats}


def config4_sharded_dryrun(repeats: int) -> dict:
    """BASELINE config 4 dryrun: the row-sharded multi-level DWT over
    the full device mesh (the 20000x20000 map-scan transform), Tier-1/2
    excluded — hence 'dryrun', not a full-encode number."""
    import jax
    import jax.numpy as jnp

    from bucketeer_tpu.parallel import make_mesh, sharded_dwt2d_forward
    from bucketeer_tpu.parallel.sharded_dwt import can_row_shard

    size = _env_int("BENCH_SHARD_SIZE", 8192, smoke=512)
    n_dev = len(jax.devices())
    levels = 5
    while levels > 1 and not can_row_shard(size, levels, max(n_dev, 2)):
        levels -= 1
    shards = n_dev if n_dev > 1 and can_row_shard(size, levels,
                                                  n_dev) else 1
    mesh = make_mesh(tile_parallel=shards)
    img = synthetic_scan16(size).astype(np.int32)

    def run():
        if shards > 1:
            ll, bands = sharded_dwt2d_forward(jnp.asarray(img), levels,
                                              True, mesh)
        else:
            from bucketeer_tpu.codec.dwt import dwt2d_forward
            ll, bands = dwt2d_forward(jnp.asarray(img), levels, True)
        jax.block_until_ready(ll)
        return ll

    run()                                                  # compile
    best, _ = _timed(run, repeats)
    mpix = size * size / 1e6
    return {"value": round(mpix / best, 3), "unit": "MPix/s",
            "seconds": round(best, 4), "dryrun": True,
            "stage": "sharded multi-level 5/3 DWT only",
            "image": f"{size}x{size} int32", "levels": levels,
            "shards": shards, "repeats": repeats}


def config5_mixed_overlap(repeats: int) -> dict:
    """BASELINE config 5 analog: mixed-size batch, 'upload' (durable
    local write, the FakeS3 stand-in) overlapped with the next encode."""
    import tempfile

    from bucketeer_tpu.codec import encoder
    from bucketeer_tpu.codec.encoder import EncodeParams

    if SMOKE and "BENCH_MIXED_SIZES" not in os.environ:
        sizes = [256, 128, 192]
    else:
        sizes = [int(s) for s in os.environ.get(
            "BENCH_MIXED_SIZES", "2048,1024,1536,768").split(",")]
    imgs = [synthetic_photo(s, seed=200 + i)
            for i, s in enumerate(sizes)]
    params = EncodeParams(lossless=False, levels=5, tile_size=1024,
                          base_delta=2.0, rate=3.0)
    for im in imgs:
        encoder.encode_jp2(im, 8, params)                  # compile all

    def upload(data: bytes, path: str) -> None:
        with open(path, "wb") as fh:
            fh.write(data)
            fh.flush()
            os.fsync(fh.fileno())

    def run():
        total = 0
        with tempfile.TemporaryDirectory() as tmp, \
                ThreadPoolExecutor(max_workers=2) as pool:
            futs = []
            for i, im in enumerate(imgs):
                data = encoder.encode_jp2(im, 8, params)
                total += len(data)
                futs.append(pool.submit(
                    upload, data, os.path.join(tmp, f"{i}.jp2")))
            for f in futs:
                f.result()
        return total

    best, total_bytes = _timed(run, repeats)
    mpix = sum(s * s for s in sizes) / 1e6
    return {"value": round(mpix / best, 3), "unit": "MPix/s",
            "seconds": round(best, 3), "sizes": sizes,
            "output_bytes": total_bytes, "repeats": repeats,
            "overlap": "upload behind encode"}


def config6_decode(repeats: int) -> dict:
    """Decode path (the GET /images read endpoint's engine): full decode
    and a reduce=2 thumbnail read of a lossless JP2, with the
    per-segment split (decode.t2_parse / mq / t1 / device_inverse).
    Host Tier-1 decode is pure Python for now, so the default size is
    modest; the segment report is what tracks where the time goes."""
    from bucketeer_tpu.codec import encoder
    from bucketeer_tpu.codec.decode import decode, set_metrics_sink
    from bucketeer_tpu.codec.encoder import EncodeParams
    from bucketeer_tpu.server.metrics import Metrics

    size = _env_int("BENCH_DECODE_SIZE", 256, smoke=96)
    img = synthetic_photo(size)
    params = EncodeParams(lossless=True, levels=4,
                          tile_size=min(128, size))
    data = encoder.encode_jp2(img, 8, params)
    decode(data)                               # warm the inverse compiles
    decode(data, reduce=2)
    sink = Metrics()
    set_metrics_sink(sink)
    try:
        best_full, full = _timed(lambda: decode(data), repeats)
        best_thumb, thumb = _timed(lambda: decode(data, reduce=2),
                                   repeats)
    finally:
        set_metrics_sink(None)
    segments = {}
    for name, st in sink.report()["stages"].items():
        if name.startswith("decode."):
            entry = {"total_s": st["total_s"]}
            for k in ("mpixels_per_s", "items_per_s", "items"):
                if k in st:
                    entry[k] = st[k]
            segments[name] = entry
    mpix = size * size / 1e6
    t_mpix = thumb.shape[0] * thumb.shape[1] / 1e6
    return {"value": round(mpix / best_full, 3), "unit": "MPix/s",
            "seconds": round(best_full, 3),
            "image": f"{size}x{size}x3 uint8 lossless",
            "input_bytes": len(data),
            "full_shape": list(full.shape),
            "thumbnail": {"reduce": 2, "shape": list(thumb.shape),
                          "seconds": round(best_thumb, 3),
                          "value": round(t_mpix / best_thumb, 3),
                          "speedup_vs_full": round(
                              best_full / best_thumb, 2)},
            "segments": segments, "repeats": repeats}


def config7_concurrent_serving(repeats: int) -> dict:
    """Concurrent serving through the cross-request encode scheduler
    (engine/scheduler.py): N closed-loop clients, each encoding R
    distinct same-shape images back to back, all through one shared
    scheduler. Reports aggregate MPix/s, per-request p50/p95 latency,
    measured device-batch occupancy (requests per merged launch), the
    serialized 1-client x N*R baseline, and byte-identity vs the serial
    encoder — the continuous-batching numbers the serving story stands
    on. With more than one visible device the scheduler's pool spreads
    launches (ISSUE 17): the report carries per-device launch counts
    and a serialized single-device-pool comparison round. Env:
    BENCH_CLIENTS, BENCH_REQS_PER_CLIENT, BENCH_SERVE_SIZE,
    BENCH_SCHED_SLOTS, BENCH_SCHED_WINDOW_MS, BENCH_SCHED_DEVICES
    (0 = every visible device)."""
    import threading

    from bucketeer_tpu.codec import encoder
    from bucketeer_tpu.codec.encoder import EncodeParams
    from bucketeer_tpu.engine.scheduler import EncodeScheduler
    from bucketeer_tpu.server.metrics import Metrics

    n_clients = _env_int("BENCH_CLIENTS", 8, smoke=4)
    per_client = _env_int("BENCH_REQS_PER_CLIENT", 3, smoke=3)
    size = _env_int("BENCH_SERVE_SIZE", 1024, smoke=192)
    window_s = float(os.environ.get("BENCH_SCHED_WINDOW_MS", "10")) / 1e3
    # Encode slots: cap concurrency at roughly the host's cores — more
    # admitted encodes than cores just thrash the GIL-bound Tier-2
    # share; the queue (not the OS scheduler) should hold the excess.
    slots = _env_int("BENCH_SCHED_SLOTS",
                     max(2, min(n_clients, (os.cpu_count() or 2) - 1)))
    devices = _env_int("BENCH_SCHED_DEVICES", 0)
    imgs = [[synthetic_photo(size, seed=300 + 16 * c + k)
             for k in range(per_client)] for c in range(n_clients)]
    flat = [im for client_imgs in imgs for im in client_imgs]
    params = EncodeParams(lossless=False, levels=4, base_delta=2.0,
                          rate=3.0)

    # Serialized baseline (and the byte-identity reference): one client
    # encoding every image back to back on the plain encoder. The first
    # encode warms the solo-batch compile; best of two passes so a
    # noisy neighbor can't sandbag the comparison either way.
    encoder.encode_jp2(flat[0], 8, params)
    serial_s = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        serial = [encoder.encode_jp2(im, 8, params) for im in flat]
        serial_s = min(serial_s, time.perf_counter() - t0)

    sched = EncodeScheduler(max_concurrent=slots,
                            queue_depth=2 * n_clients,
                            window_s=window_s,
                            devices=devices or None)
    sink = Metrics()

    def round_trip(s=None) -> tuple:
        s = s if s is not None else sched
        outs = [[None] * per_client for _ in range(n_clients)]
        lats: list = []
        errs: list = []
        barrier = threading.Barrier(n_clients)

        def client(c: int) -> None:
            barrier.wait()
            for k in range(per_client):
                c0 = time.perf_counter()
                try:
                    outs[c][k] = s.encode_jp2(imgs[c][k], 8, params)
                except BaseException as exc:
                    errs.append(exc)
                    return
                lats.append(time.perf_counter() - c0)

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(n_clients)]
        w0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errs:
            # A silently dead client would turn a regression into a
            # bogus-but-green data point; fail the config instead.
            raise errs[0]
        return time.perf_counter() - w0, outs, lats

    round_trip()                 # warm the merged-bucket compiles
    # Sink attached after warmup: the server-side request histogram
    # then covers exactly the measured rounds, so its p95 is
    # comparable 1:1 with the client-side all-round percentile.
    sched.set_metrics_sink(sink)
    best, outs, lats = None, None, None
    all_lats: list = []
    for _ in range(max(repeats, 3)):
        wall, o, l = round_trip()
        all_lats.extend(l)
        if best is None or wall < best:
            best, outs, lats = wall, o, l
    # Single-device-pool comparison round (ISSUE 17): same clients and
    # images with the pool pinned to one device — the floor the
    # multi-device aggregate throughput must not fall below.
    sched1 = EncodeScheduler(max_concurrent=slots,
                             queue_depth=2 * n_clients,
                             window_s=window_s, devices=1)
    try:
        round_trip(sched1)       # warm this pool's merge window shape
        single_best = min(round_trip(sched1)[0] for _ in range(2))
    finally:
        sched1.close()
    try:
        lats_ms = sorted(x * 1e3 for x in lats)
        rep = sink.report()
        occ = rep.get("values", {}).get("encode.batch_occupancy",
                                        {"count": 0, "mean": 0, "max": 0})
        counters = rep.get("counters", {})
        qw = rep["stages"].get("encode.queue_wait", {})
        flat_out = [o for client_outs in outs for o in client_outs]
        mpix = len(flat) * size * size / 1e6
        # ISSUE 14 gate: the new server-side request-latency histogram
        # (encode.request, /metrics p95) must agree with what clients
        # actually measured across the same rounds.
        all_ms = sorted(x * 1e3 for x in all_lats)
        client_p95_ms = all_ms[min(len(all_ms) - 1,
                                   int(len(all_ms) * 0.95))]
        server_p95_ms = rep["stages"].get("encode.request",
                                          {}).get("p95_ms")
        _assert_p95_agreement(server_p95_ms, client_p95_ms,
                              "7_concurrent_serving")
        return {
            "value": round(mpix / best, 3), "unit": "MPix/s",
            "seconds": round(best, 3), "clients": n_clients,
            "requests_per_client": per_client, "slots": slots,
            "image": f"{size}x{size}x3 uint8 rate=3",
            "p50_ms": round(lats_ms[len(lats_ms) // 2], 1),
            "p95_ms": round(lats_ms[min(len(lats_ms) - 1,
                                        int(len(lats_ms) * 0.95))], 1),
            "serialized_seconds": round(serial_s, 3),
            "speedup_vs_serialized": round(serial_s / best, 2),
            "occupancy": {"mean": occ["mean"], "max": occ["max"],
                          "launches": occ["count"]},
            "devices": sched.pool_report().get("devices"),
            "device_launches": {
                k.rsplit(".", 1)[-1]: v for k, v in counters.items()
                if k.startswith("encode.device_launches.d")},
            "distinct_devices": sum(
                1 for k in counters
                if k.startswith("encode.device_launches.d")),
            "single_device_pool_seconds": round(single_best, 3),
            "speedup_vs_single_device_pool": round(single_best / best,
                                                   2),
            "queue_wait_ms": round(
                1e3 * qw.get("total_s", 0.0) / max(1, qw.get("count", 1)),
                2),
            "admission_rejects": counters.get("encode.admission_rejects",
                                              0),
            "byte_identical": all(a == b
                                  for a, b in zip(serial, flat_out)),
            "server_p95_ms": round(server_p95_ms, 1),
            "client_p95_all_rounds_ms": round(client_p95_ms, 1),
            "stage_percentiles": _stage_percentiles(sink, ("encode.",)),
            "repeats": repeats,
        }
    finally:
        sched.close()


def config8_tile_storm(repeats: int) -> dict:
    """Closed-loop tile-request storm against the random-access read
    path (the GET /images?region= engine): N clients pull tile regions
    of a stored derivative through the shared scheduler at read
    priority. Two phases — cache-cold (every tile distinct: index
    build + indexed Tier-2 + windowed Tier-1/inverse) and cache-warm
    (same tiles again: decoded-tile LRU hits) — reporting aggregate
    tiles/s and p50/p95 latency per phase against the whole-image-decode
    baseline (what serving a tile cost before random access). Env:
    BENCH_STORM_SIZE, BENCH_STORM_TILE, BENCH_STORM_CLIENTS."""
    import dataclasses
    import queue as queue_mod
    import tempfile
    import threading

    from bucketeer_tpu.codec import encoder
    from bucketeer_tpu.codec.decode import decode, set_metrics_sink
    from bucketeer_tpu.codec.encoder import EncodeParams
    from bucketeer_tpu.converters.reader import TpuReader
    from bucketeer_tpu.engine.scheduler import Scheduler
    from bucketeer_tpu.server.metrics import Metrics

    size = _env_int("BENCH_STORM_SIZE", 1024, smoke=256)
    tile = _env_int("BENCH_STORM_TILE", max(64, size // 8), smoke=64)
    clients = _env_int("BENCH_STORM_CLIENTS", 4, smoke=4)
    img = synthetic_photo(size)
    # The reference recipe (RPCL + PLT + R tile-parts): the index build
    # takes the PLT arithmetic path, no header walk.
    params = dataclasses.replace(
        EncodeParams.kakadu_recipe(lossless=False, rate=3.0),
        tile_size=min(512, size))
    data = encoder.encode_jp2(img, 8, params)

    # Whole-image-decode baseline: what one tile request costs when the
    # server can only decode everything and crop.
    decode(data)                                   # warm the compiles
    base_s, full = _timed(lambda: decode(data), max(1, repeats))

    tiles = [(x, y, tile, tile)
             for y in range(0, size, tile) for x in range(0, size, tile)]

    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "storm.jp2")
        with open(path, "wb") as fh:
            fh.write(data)
        sink = Metrics()
        sched = Scheduler(max_concurrent=max(2, clients),
                          queue_depth=4 * clients)
        sched.set_metrics_sink(sink)
        set_metrics_sink(sink)
        reader = TpuReader(cache_mb=256, metrics=sink, scheduler=sched)

        def run_phase(check_against=None) -> dict:
            work: queue_mod.Queue = queue_mod.Queue()
            for t in tiles:
                work.put(t)
            lats: list = []
            errs: list = []
            lock = threading.Lock()

            def client() -> None:
                while True:
                    try:
                        region = work.get_nowait()
                    except queue_mod.Empty:
                        return
                    t0 = time.perf_counter()
                    try:
                        out = reader.read(path, region=region)
                    except BaseException as exc:
                        errs.append(exc)
                        return
                    dt = time.perf_counter() - t0
                    with lock:
                        lats.append(dt)
                    if check_against is not None:
                        x, y, w, h = region
                        if not np.array_equal(
                                out, check_against[y:y + h, x:x + w]):
                            errs.append(AssertionError(
                                f"tile {region} not bit-exact"))

            threads = [threading.Thread(target=client)
                       for _ in range(clients)]
            w0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - w0
            if errs:
                raise errs[0]
            lats.sort()
            return {"tiles": len(lats),
                    "seconds": round(wall, 3),
                    "tiles_per_s": round(len(lats) / wall, 2),
                    "p50_ms": round(1e3 * lats[len(lats) // 2], 1),
                    "p95_ms": round(
                        1e3 * lats[min(len(lats) - 1,
                                       int(len(lats) * 0.95))], 1),
                    "mean_ms": round(1e3 * sum(lats) / len(lats), 2)}

        try:
            # Warm the region-inverse compiles (one pass), then drop
            # both cache tiers so the cold phase measures the whole
            # random-access path (index build included), not XLA
            # compilation.
            run_phase()
            reader.reset_caches(tiles=True, index=True)
            sink2 = Metrics()
            sched.set_metrics_sink(sink2)
            reader.metrics = sink2
            set_metrics_sink(sink2)
            cold = run_phase(check_against=full)
            warm = run_phase()
            rep = sink2.report()
            counters = rep.get("counters", {})
            # ISSUE 14 gate: server-side decode.request p95 (histogram;
            # only cache misses reach the scheduler, so it covers
            # exactly the cold phase) vs the cold clients' own p95.
            server_p95_ms = rep["stages"].get("decode.request",
                                              {}).get("p95_ms")
            _assert_p95_agreement(server_p95_ms, cold["p95_ms"],
                                  "8_tile_storm")
        finally:
            set_metrics_sink(None)
            sched.close()

    # Aggregate serving throughput vs a whole-image-decode server on
    # the same hardware (which, like us, is GIL-bound across clients):
    # it serves at most 1/full_s tiles/s however many clients connect.
    speedup = cold["tiles_per_s"] * base_s
    return {
        "value": cold["tiles_per_s"], "unit": "tiles/s",
        "seconds": cold["seconds"],
        "image": f"{size}x{size}x3 uint8 rate=3",
        "tile": f"{tile}x{tile}",
        "tile_area_fraction": round(tile * tile / (size * size), 5),
        "clients": clients,
        "cold": cold, "warm": warm,
        "full_decode_baseline_s": round(base_s, 3),
        "speedup_vs_full_decode": round(speedup, 2),
        "region_blocks": counters.get("decode.region_blocks", 0),
        "cache": {
            "tile_hits": counters.get("decode.cache_hits", 0),
            "tile_misses": counters.get("decode.cache_misses", 0),
            "index_hits": counters.get("decode.index_cache_hits", 0),
            "index_misses": counters.get("decode.index_cache_misses", 0),
        },
        "admission_rejects": counters.get("decode.admission_rejects", 0),
        # Least-loaded pool placement of decode request threads
        # (ISSUE 17): which devices served the cold-phase reads.
        "device_assigned": {
            k.rsplit(".", 1)[-1]: v for k, v in counters.items()
            if k.startswith("decode.device_assigned.d")},
        "server_p95_ms": round(server_p95_ms, 1),
        "stage_profile": _stage_profile(sink2, ("decode.",)),
        "stage_percentiles": _stage_percentiles(sink2, ("decode.",)),
        "repeats": repeats,
    }


def config9_batch_dataplane(repeats: int) -> dict:
    """The batch data plane (ISSUE 19): one submit_batchread through a
    1-device scheduler pool (per-item fan-out + merged dequant
    launches + sharded placement) vs the decode-then-stack baseline a
    client would write against the same server: N admitted reads
    (sched.read -> decode_to_coefficients), host materialize,
    np.stack, re-upload.  Both paths pay the identical Tier-1 entropy
    decode, so the margin is the serving overhead the batch plane
    amortizes — one admission instead of N, one merged dequant launch
    instead of N dispatches, one device-side stack instead of a
    host round-trip.  Reports batches/s, bytes/s and the speedup at
    2-3 batch sizes, the merged-launch occupancy and per-device
    launch spread from the scheduler's own ledger, plus a
    byte-identity check of the batched bands against the baseline
    stack. Env: BENCH_BATCHPLANE_SIZE (image edge),
    BENCH_BATCHPLANE_NS (comma list of batch sizes)."""
    import jax

    from bucketeer_tpu import batches as batches_mod
    from bucketeer_tpu import tensor as tensor_mod
    from bucketeer_tpu.codec import encoder
    from bucketeer_tpu.codec.encoder import EncodeParams
    from bucketeer_tpu.engine.scheduler import EncodeScheduler
    from bucketeer_tpu.server.metrics import Metrics

    # Training-crop-sized tiles: the batch plane amortizes per-request
    # serving overhead (admission, spans, dequant dispatch, host
    # round-trip), so the margin over decode-then-stack is largest
    # where per-item decode work is small — which is exactly the
    # data-loader regime (small coefficient crops, big N).
    size = _env_int("BENCH_BATCHPLANE_SIZE", 64, smoke=32)
    ns_spec = os.environ.get("BENCH_BATCHPLANE_NS",
                             "4,8" if SMOKE else "2,4,8")
    sizes = [int(s) for s in ns_spec.split(",") if s.strip()]
    n_max = max(sizes)

    params = EncodeParams(lossless=True, levels=2,
                          tile_size=min(128, size))
    blobs = {}
    for i in range(n_max):
        blobs[f"img{i}"] = encoder.encode_jp2(
            synthetic_photo(size, seed=1901 + i), 8, params)

    # A generous merge window costs full groups nothing (the worker
    # breaks out the moment the advertised fan-out width arrives) but
    # keeps one GIL-straggler item from splitting the merged launch.
    sched = EncodeScheduler(queue_depth=32, max_concurrent=16,
                            devices=1, window_s=0.3)

    def serve_one(blob):
        """One per-image coefficient read as the serving tier delivers
        it: admitted interactive read, bands materialized into the npz
        payload a GET response carries."""
        import io

        cs = sched.read(tensor_mod.decode_to_coefficients, blob)
        buf = io.BytesIO()
        np.savez(buf, **{f"r{res}_{name}": arr
                         for (res, name), arr in cs.to_host().items()})
        return buf.getvalue()

    def baseline(ids):
        """Decode-then-stack: what a training loader does without the
        batch plane — N per-image tensor reads across the serving
        boundary (each an admitted read returning its npz payload),
        parsed client-side, stacked on host, re-uploaded as the batch
        tensor. The batch path's consumer keeps the sharded device
        arrays instead, so it pays none of this per image."""
        import io

        def parse(payload):
            out = {}
            for name, arr in np.load(io.BytesIO(payload)).items():
                res, band = name[1:].split("_", 1)
                out[(int(res), band)] = arr
            return out

        hosts = [parse(serve_one(blobs[i])) for i in ids]
        return {key: jax.device_put(
                    np.stack([h[key] for h in hosts]))
                for key in hosts[0]}

    sink = Metrics()
    sched.set_metrics_sink(sink)
    # The margin is a few percent of a decode-bound total: min-of-1
    # is inside the noise floor, so impose a local repeats floor.
    repeats = max(repeats, 7)
    per_size = {}
    try:
        for n in sizes:
            ids = [f"img{i}" for i in range(n)]
            recipe = batches_mod.parse_recipe({"ids": ids})
            # Warm compiles on both paths before timing.
            result = sched.submit_batchread(
                batches_mod.assemble_batch, recipe,
                data_for=blobs.get)
            base = baseline(ids)
            # Byte identity: the sharded batch must equal the stacked
            # per-image reads bit for bit.
            host = result.to_host()
            identical = all(
                np.array_equal(host[key], np.asarray(base[key]))
                for key in host)
            if not identical:
                raise AssertionError(
                    f"batch path diverged from decode-then-stack "
                    f"at N={n}")
            # Interleave the reps: this box's wall-clock drifts by
            # tens of percent between runs, so alternating paths puts
            # both mins under the same weather instead of timing one
            # path entirely inside a bad stretch.
            best_batch = best_base = float("inf")
            for _ in range(repeats):
                t0 = time.perf_counter()
                result = sched.submit_batchread(
                    batches_mod.assemble_batch, recipe,
                    data_for=blobs.get)
                best_batch = min(best_batch,
                                 time.perf_counter() - t0)
                t0 = time.perf_counter()
                baseline(ids)
                best_base = min(best_base,
                                time.perf_counter() - t0)
            nbytes = result.nbytes
            per_size[str(n)] = {
                "batch_seconds": round(best_batch, 4),
                "baseline_seconds": round(best_base, 4),
                "ratio": round(best_base / best_batch, 3),
                "batches_per_s": round(1.0 / best_batch, 3),
                "mb_per_s": round(nbytes / 1e6 / best_batch, 3),
                "baseline_mb_per_s": round(
                    nbytes / 1e6 / best_base, 3),
                "batch_bytes": int(nbytes),
                "layout": result.layout,
            }
    finally:
        sched.close()

    report = sink.report()
    occ = report.get("values", {}).get("batchread.batch_occupancy", {})
    counters = report.get("counters", {})
    spread = {k.rsplit(".", 1)[1]: v for k, v in counters.items()
              if k.startswith("batchread.device_launches.d")}
    head = per_size[str(n_max)]
    return {
        "value": head["ratio"], "unit": "x vs decode-then-stack",
        "seconds": head["batch_seconds"],
        "image": f"{size}x{size}x3 uint8 lossless L2",
        "byte_identity": True,
        "batch_sizes": per_size,
        "merged_launch_occupancy_max": occ.get("max", 0),
        "merged_launch_occupancy_mean": occ.get("mean", 0),
        "device_launches": counters.get(
            "batchread.device_launches", 0),
        "device_spread": spread,
        "merged_images": counters.get("batchread.merged_images", 0),
        "repeats": repeats,
    }


def config10_tensor_codec(repeats: int) -> dict:
    """Compressed-domain tensor delivery (ISSUE 13), both products.

    (a) coefficient reads: decode_to_coefficients (Tier-1 + dequant,
    device-resident subbands) vs a full pixel decode of the same
    stream — coefficient MB/s and the read speedup from skipping the
    inverse DWT / color transform.
    (b) the tensor codec: encode_tensor/decode_tensor MB/s and the
    compression ratio vs np.savez_compressed on the same array. The
    device-MQ chain is sequential-scan-bound on CPU (the graftcost
    elephant), so the CPU sweep codes a low-plane int8 workload;
    BENCH_TENSOR_FLOAT=1 (or a real accelerator) adds the float32
    checkpoint-style workload. Env: BENCH_COEFF_SIZE,
    BENCH_TENSOR_ELEMS, BENCH_TENSOR_BACKEND (device|replay|host)."""
    import io

    from bucketeer_tpu import tensor as tensor_mod
    from bucketeer_tpu.codec import encoder
    from bucketeer_tpu.codec.decode import decode
    from bucketeer_tpu.codec.encoder import EncodeParams

    # --- (a) coefficient reads vs full decode --------------------------
    size = _env_int("BENCH_COEFF_SIZE", 256, smoke=96)
    img = synthetic_photo(size)
    params = EncodeParams(lossless=True, levels=4,
                          tile_size=min(128, size))
    data = encoder.encode_jp2(img, 8, params)
    cs = tensor_mod.decode_to_coefficients(data)      # warm compiles
    decode(data)
    best_coeff, cs = _timed(
        lambda: tensor_mod.decode_to_coefficients(data), repeats)
    best_full, _ = _timed(lambda: decode(data), repeats)
    coeff_mb = cs.nbytes / 1e6
    coefficients = {
        "image": f"{size}x{size}x3 uint8 lossless",
        "coefficient_bytes": cs.nbytes,
        "seconds": round(best_coeff, 3),
        "mb_per_s": round(coeff_mb / best_coeff, 3),
        "full_decode_seconds": round(best_full, 3),
        "speedup_vs_full_decode": round(best_full / best_coeff, 3),
        "bands": len(cs.bands),
    }

    # --- (b) the tensor codec ------------------------------------------
    backend = os.environ.get("BENCH_TENSOR_BACKEND", "device")
    n = _env_int("BENCH_TENSOR_ELEMS", 16384, smoke=8192)
    rng = np.random.default_rng(1013)
    workloads = {
        # Quantized-checkpoint-like: low-entropy small-range int8 —
        # few magnitude planes, so the sequential device scans stay
        # affordable on the CPU backend too.
        "int8_quantized": (rng.normal(0.0, 2.0, size=n)
                           .clip(-7, 7).round().astype(np.int8)),
    }
    if os.environ.get("BENCH_TENSOR_FLOAT", "") not in ("", "0"):
        workloads["float32_weights"] = (
            rng.standard_normal(n).astype(np.float32) * 0.02)
    tensors = {}
    for name, arr in workloads.items():
        blob = tensor_mod.encode_tensor(arr, device=backend)  # warm
        best_enc, blob = _timed(
            lambda a=arr: tensor_mod.encode_tensor(a, device=backend),
            repeats)
        best_dec, out = _timed(
            lambda b=blob: tensor_mod.decode_tensor(b), repeats)
        if not np.array_equal(
                out.view((np.uint8, out.dtype.itemsize)),
                arr.view((np.uint8, arr.dtype.itemsize))):
            raise AssertionError(f"{name}: lossy roundtrip")
        buf = io.BytesIO()
        np.savez_compressed(buf, arr=arr)
        mb = arr.nbytes / 1e6
        tensors[name] = {
            "elements": int(arr.size),
            "raw_bytes": int(arr.nbytes),
            "coded_bytes": len(blob),
            "ratio": round(arr.nbytes / len(blob), 3),
            "savez_bytes": buf.getbuffer().nbytes,
            "ratio_vs_savez": round(
                buf.getbuffer().nbytes / len(blob), 3),
            "encode_mb_per_s": round(mb / best_enc, 4),
            "decode_mb_per_s": round(mb / best_dec, 4),
            "backend": backend,
        }

    head = tensors["int8_quantized"]
    return {
        "value": head["encode_mb_per_s"], "unit": "MB/s",
        "seconds": round(head["raw_bytes"] / 1e6
                         / max(head["encode_mb_per_s"], 1e-9), 3),
        "coefficients": coefficients,
        "tensors": tensors,
        "repeats": repeats,
    }


CONFIGS = {
    "1_single_4k_rate3": config1_single_4k,
    "2_batch_2k_lossy": config2_batch_2k,
    "3_lossless_16bit": config3_lossless16,
    "4_sharded_dwt_dryrun": config4_sharded_dryrun,
    "5_mixed_upload_overlap": config5_mixed_overlap,
    "6_decode_roundtrip": config6_decode,
    "7_concurrent_serving": config7_concurrent_serving,
    "8_tile_storm": config8_tile_storm,
    "9_batch_dataplane": config9_batch_dataplane,
    "10_tensor_codec": config10_tensor_codec,
}


def main() -> int:
    from bucketeer_tpu.converters.tpu import (compile_cache_entries,
                                              enable_compile_cache)

    cache_dir = enable_compile_cache()
    entries_before = compile_cache_entries()
    backend = init_backend()
    repeats = _env_int("BENCH_REPEATS", 3, smoke=1)
    wanted = os.environ.get("BENCH_CONFIGS", "")
    selected = ({k: f for k, f in CONFIGS.items()
                 if k.split("_")[0] in wanted.split(",")} if wanted
                else CONFIGS)

    results = {name: fn(repeats) for name, fn in selected.items()}

    entries_after = compile_cache_entries()
    value = results.get("1_single_4k_rate3", {}).get("value", 0.0)
    print(json.dumps({
        "metric": "lossy_jp2_encode_throughput",
        "value": value,
        "unit": "MPix/s",
        "vs_baseline": round(value / BASELINE_MPIX_S, 4),
        "platform": backend["platform"],
        "n_devices": backend["n_devices"],
        "backend": backend,
        # Coarse machine class for the regression gate: wall-clock
        # throughput is only comparable between runs of the same class
        # (hosted-runner vs dev-box variance alone exceeds the gate's
        # loss threshold).
        "machine": {"arch": platform_mod.machine(),
                    "cpu_count": os.cpu_count()},
        "smoke": SMOKE,
        "compile_cache": {
            "dir": cache_dir,
            "entries_before": entries_before,
            "entries_after": entries_after,
            # 0 new entries = every program was a cache hit; anything
            # else counts the misses persisted.
            "misses_persisted": max(0, entries_after - entries_before),
        },
        "configs": results,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
