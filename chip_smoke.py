"""Smoke run of the encode and serve path on the chip.

One process, the only one that touches JAX, drives the product through
the entry points a user calls: the ``Engine`` and aiohttp app built as
``server/main.py`` builds them, served on a localhost port in this
process and driven over HTTP, with uploads going to the fake S3 store
under a temporary ``BUCKETEER_TMPDIR``. Every input is made from
``--seed``; every phase checks its output against an independent
reference (PIL's OpenJPEG decode, or the source array).

``python chip_smoke.py`` needs one TPU chip and runs, in order:

1. single-image lossy: a 4096x4096 RGB 8-bit TIFF through
   ``/images/{id}/{path}`` with the reference's Kakadu recipe
   (``-rate 3``, 512x512 tiles, 6 levels, RPCL, 6 layers); PIL's decode
   of the upload must reach 30 dB PSNR against the source;
2. single-image lossless: a 4096x4096 16-bit grayscale scan (BASELINE
   config 3) through the same endpoint; PIL's decode must equal it;
3. CSV batch: a 4-item CSV of 2048x2048 RGB TIFFs (BASELINE config 2,
   fewer items) in local (no-lambda) mode; the job must finalize with
   every item ``succeeded`` and every JPX must decode;
4. region read: the lossless derivative, seeded back under
   ``$BUCKETEER_TMPDIR/bucketeer/``, read through
   ``GET /images/{id}?region=1024,1024,512,512&format=raw``; the answer
   must equal that crop of the source.

``python chip_smoke.py --chips 4`` runs only the four-chip path and what
it is compared with: an 8192x8192 RGB lossless image routed through the
converter's device mesh, byte for byte against the same image encoded
on one device, and 8 concurrent requests over a 4-device scheduler
pool, byte for byte against serial encodes.

Tier-1 must run where the deployment says: a code-block coded by the
pure-Python fallback (a native library that did not load, or loaded
late) fails the run.

Wall times printed here are smoke timings, not a benchmark. The last
line of standard output is one JSON object naming the device; it is
printed only when every phase passed on a TPU.
"""
from __future__ import annotations

import argparse
import asyncio
import csv
import io
import json
import os
import sys
import tempfile
import time
import urllib.parse

import numpy as np

REGION = (1024, 1024, 512, 512)     # x, y, w, h
BUCKET = "bucketeer"                # the S3 worker's default bucket


class SmokeFailure(RuntimeError):
    """A phase produced output that fails its check."""


def _check(ok: bool, message: str) -> None:
    if not ok:
        raise SmokeFailure(message)


# --- inputs, made from the seed ------------------------------------------

def photo_rgb(rng: np.random.Generator, h: int, w: int) -> np.ndarray:
    """Photograph-like 8-bit RGB: smooth gradients, texture, edges."""
    y = np.arange(h, dtype=np.float32)[:, None]
    x = np.arange(w, dtype=np.float32)[None, :]
    base = (128 + 80 * np.sin(2 * np.pi * x / w * 3)
            * np.cos(2 * np.pi * y / h * 2))
    edges = ((x // 256 + y // 256) % 2) * 24
    out = np.empty((h, w, 3), np.uint8)
    for c, (gain, offset) in enumerate(((1.0, 0), (0.8, 30), (0.6, 60))):
        tex = rng.normal(0, 6, (h, w)).astype(np.float32)
        out[..., c] = np.clip(base * gain + offset + tex + edges, 0, 255)
    return out


def scan_gray16(rng: np.random.Generator, h: int, w: int) -> np.ndarray:
    """16-bit grayscale archival-scan-like content with film grain."""
    y = np.arange(h, dtype=np.float32)[:, None]
    x = np.arange(w, dtype=np.float32)[None, :]
    base = 32768 + 18000 * np.sin(x / 37.0) * np.cos(y / 29.0)
    grain = rng.normal(0, 300, (h, w)).astype(np.float32)
    return np.clip(base + grain, 0, 65535).astype(np.uint16)


def write_tiff(path: str, arr: np.ndarray) -> str:
    from PIL import Image

    Image.fromarray(arr).save(path, format="TIFF")
    return path


def pil_decode(data: bytes) -> np.ndarray:
    """The independent reference decoder: PIL's OpenJPEG."""
    from PIL import Image

    with Image.open(io.BytesIO(data)) as im:
        return np.asarray(im)


def psnr(ref: np.ndarray, got: np.ndarray, peak: float = 255.0) -> float:
    err = np.mean((ref.astype(np.float64) - got.astype(np.float64)) ** 2)
    return float("inf") if err == 0 else 10 * np.log10(peak ** 2 / err)


# --- the served system ---------------------------------------------------

class Server:
    """One deployment: a properties file, then the Engine and app built
    as ``server/main.py`` builds them, listening on a localhost port of
    this process."""

    def __init__(self, workdir: str, name: str, **props) -> None:
        self.workdir = workdir
        self.images = os.path.join(workdir, "images")
        self.csv_out = os.path.join(workdir, "csv-out")
        flags = os.path.join(workdir, "features.conf")
        with open(flags, "w", encoding="utf-8") as fh:
            fh.write("bucketeer.fs.write.csv = true\n")
        props = {"bucketeer.fs.image.mount": self.images,
                 "bucketeer.fs.csv.mount": self.csv_out,
                 "bucketeer.iiif.url": "http://iiif.localhost/iiif",
                 "feature.flags": flags,
                 "s3.requeue.delay": "0.05", **props}
        self.config_path = os.path.join(workdir, f"{name}.properties")
        with open(self.config_path, "w", encoding="utf-8") as fh:
            fh.writelines(f"{k}={v}\n" for k, v in props.items())
        os.makedirs(self.images, exist_ok=True)
        self.runner = None
        self.session = None
        self.base = ""

    async def __aenter__(self) -> "Server":
        import aiohttp
        from aiohttp import web

        from bucketeer_tpu import config as cfg
        from bucketeer_tpu import job_factory
        from bucketeer_tpu.engine import Engine
        from bucketeer_tpu.server.app import build_app
        from bucketeer_tpu.utils import path_prefix as pp

        config = cfg.Config.load(self.config_path)
        mount = config.get_str(cfg.FILESYSTEM_IMAGE_MOUNT) or ""
        job_factory.set_path_prefix(
            pp.get_prefix(config.get_str(cfg.FILESYSTEM_PREFIX), mount))
        self.engine = Engine(config)
        self.runner = web.AppRunner(build_app(self.engine))
        await self.runner.setup()
        site = web.TCPSite(self.runner, "127.0.0.1", 0)
        await site.start()
        port = self.runner.addresses[0][1]
        self.base = f"http://127.0.0.1:{port}"
        self.session = aiohttp.ClientSession(
            timeout=aiohttp.ClientTimeout(total=1800))
        return self

    async def __aexit__(self, *exc) -> None:
        await self.session.close()
        await self.runner.cleanup()

    def s3_object(self, image_id: str) -> str:
        """Where the fake store keeps an upload: the S3 key is the
        derivative's file name (the URL-encoded id plus ``.jpx``)."""
        from bucketeer_tpu.converters import output_path

        return os.path.join(os.environ["BUCKETEER_TMPDIR"],
                            "bucketeer-fake-s3", BUCKET,
                            os.path.basename(output_path(image_id, ".jpx")))

    async def load_image(self, image_id: str, path: str) -> None:
        url = (f"{self.base}/images/{urllib.parse.quote(image_id, safe='')}"
               f"/{urllib.parse.quote(path, safe='')}")
        async with self.session.get(url) as resp:
            body = await resp.text()
            _check(resp.status == 201,
                   f"load {image_id}: HTTP {resp.status}: {body[:300]}")

    async def uploaded(self, image_id: str, timeout: float = 600) -> bytes:
        """The JPX the S3 worker put, once the put finished (the worker
        deletes the local derivative after its put)."""
        from bucketeer_tpu.converters import output_path

        obj = self.s3_object(image_id)
        local = output_path(image_id, ".jpx")
        deadline = time.monotonic() + timeout
        while not (os.path.exists(obj) and not os.path.exists(local)):
            _check(time.monotonic() < deadline,
                   f"{image_id}: no upload after {timeout} s")
            await asyncio.sleep(0.05)
        with open(obj, "rb") as fh:
            return fh.read()

    async def metrics(self) -> dict:
        async with self.session.get(f"{self.base}/metrics") as resp:
            _check(resp.status == 200, f"/metrics: HTTP {resp.status}")
            return await resp.json()


# --- phases --------------------------------------------------------------

async def phase_lossy(srv: Server, seed: int, size: int) -> dict:
    src = photo_rgb(np.random.default_rng(seed), size, size)
    path = write_tiff(os.path.join(srv.images, "lossy.tif"), src)
    await srv.load_image("smoke-lossy", path)
    data = await srv.uploaded("smoke-lossy")
    got = pil_decode(data)
    _check(got.shape == src.shape, f"lossy decode shape {got.shape}")
    db = psnr(src, got)
    _check(db >= 30.0, f"lossy PSNR {db:.3f} dB < 30 dB")
    return {"psnr_db": db, "bytes": len(data),
            "bpp": len(data) * 8 / (size * size)}


async def phase_lossless(srv: Server, seed: int, size: int) -> tuple:
    src = scan_gray16(np.random.default_rng(seed + 1), size, size)
    path = write_tiff(os.path.join(srv.images, "scan16.tif"), src)
    await srv.load_image("smoke-scan16", path)
    data = await srv.uploaded("smoke-scan16")
    got = pil_decode(data)
    _check(got.dtype == np.uint16 and np.array_equal(got, src),
           f"lossless decode differs (dtype {got.dtype}, "
           f"shape {got.shape})")
    return {"bit_exact": True, "bytes": len(data)}, src, data


async def phase_csv(srv: Server, seed: int, size: int, n: int) -> dict:
    from aiohttp import FormData

    ids = [f"ark:/21198/smoke{i}" for i in range(n)]
    rows = ["Item ARK,File Name"]
    for i, image_id in enumerate(ids):
        arr = photo_rgb(np.random.default_rng(seed + 10 + i), size, size)
        write_tiff(os.path.join(srv.images, f"batch{i}.tif"), arr)
        rows.append(f"{image_id},batch{i}.tif")
    form = FormData()
    form.add_field("csvFileToUpload", ("\n".join(rows) + "\n").encode(),
                   filename="smoke-job.csv", content_type="text/csv")
    form.add_field("slack-handle", "smoke")
    async with srv.session.post(f"{srv.base}/batch/input/csv",
                                data=form) as resp:
        _check(resp.status == 200, f"CSV upload: HTTP {resp.status}")
    out = os.path.join(srv.csv_out, "smoke-job.csv")
    deadline = time.monotonic() + 1200
    while not os.path.exists(out):
        _check(time.monotonic() < deadline, "CSV job never finalized")
        await asyncio.sleep(0.1)
    with open(out, newline="", encoding="utf-8") as fh:
        states = {r["Item ARK"]: r["Bucketeer State"]
                  for r in csv.DictReader(fh)}
    _check(states == {i: "succeeded" for i in ids},
           f"CSV job states {states}")
    for image_id in ids:
        got = pil_decode(await srv.uploaded(image_id))
        _check(got.shape == (size, size, 3),
               f"{image_id}: decode shape {got.shape}")
    return {"items": n, "succeeded": n}


async def phase_region(srv: Server, src: np.ndarray, data: bytes,
                       region: tuple) -> dict:
    from bucketeer_tpu.converters import output_path

    image_id = "smoke-scan16"
    with open(output_path(image_id, ".jpx"), "wb") as fh:
        fh.write(data)
    x, y, w, h = region
    url = (f"{srv.base}/images/{image_id}"
           f"?region={x},{y},{w},{h}&format=raw")
    async with srv.session.get(url) as resp:
        body = await resp.read()
        _check(resp.status == 200, f"region read: HTTP {resp.status}")
    got = np.load(io.BytesIO(body))
    _check(np.array_equal(got, src[y:y + h, x:x + w]),
           f"region read differs from the source crop (shape "
           f"{got.shape})")
    return {"bit_exact": True, "region": list(region)}


def phase_mesh(workdir: str, seed: int, size: int,
               mesh_min_pixels: int | None = None) -> dict:
    """An over-threshold lossless image through the converter's mesh
    route, byte for byte against the same image on one device."""
    import jax

    from bucketeer_tpu.codec.encoder import EncodeParams
    from bucketeer_tpu.converters import Conversion
    from bucketeer_tpu.converters.tpu import TpuConverter

    src = photo_rgb(np.random.default_rng(seed + 2), size, size)
    path = write_tiff(os.path.join(workdir, "map.tif"), src)
    conv = TpuConverter(mesh_min_pixels=mesh_min_pixels)
    params = EncodeParams.kakadu_recipe(lossless=True)
    mesh = conv._choose_mesh(size, size, params)
    _check(mesh is not None and mesh.size == len(jax.devices()),
           f"{size}x{size} did not route through the device mesh")
    with open(conv.convert("smoke-map", path, Conversion.LOSSLESS),
              "rb") as fh:
        sharded = fh.read()
    # Work on every chip: each device's peak after the sharded encode.
    peaks = [d.memory_stats()["peak_bytes_in_use"]
             if d.memory_stats() else None for d in jax.devices()]
    _check(all(p is None or p > 0 for p in peaks),
           f"a device held nothing during the mesh encode: {peaks}")
    single_conv = TpuConverter(mesh_min_pixels=0)
    with open(single_conv.convert("smoke-map-1", path,
                                  Conversion.LOSSLESS), "rb") as fh:
        single = fh.read()
    _check(sharded == single, "mesh encode differs from the one-device "
           f"encode ({len(sharded)} vs {len(single)} bytes)")
    return {"mesh": dict(mesh.shape), "byte_identical": True,
            "bytes": len(sharded),
            "peak_bytes_in_use_after_mesh": peaks}


async def _launches(srv: Server) -> dict:
    counters = (await srv.metrics()).get("counters", {})
    return {k.rsplit(".", 1)[1]: n for k, n in counters.items()
            if k.startswith("encode.device_launches.d")}


async def phase_pool(srv: Server, seed: int, size: int, n: int) -> dict:
    """n concurrent single-image requests over the scheduler pool, byte
    for byte against serial encodes through the same converter."""
    ids = [f"smoke-pool{i}" for i in range(n)]
    paths = [write_tiff(os.path.join(srv.images, f"pool{i}.tif"),
                        photo_rgb(np.random.default_rng(seed + 20 + i),
                                  size, size))
             for i in range(n)]
    before = await _launches(srv)
    await asyncio.gather(*(srv.load_image(i, p)
                           for i, p in zip(ids, paths)))
    served = [await srv.uploaded(i) for i in ids]
    after = await _launches(srv)
    launches = {d: n - before.get(d, 0) for d, n in after.items()}
    _check(sum(1 for n in launches.values() if n) >= 2,
           f"pool launches on fewer than 2 devices: {launches}")
    for image_id, path, got in zip(ids, paths, served):
        out = await asyncio.to_thread(srv.engine.converter.convert,
                                      f"{image_id}-serial", path)
        with open(out, "rb") as fh:
            _check(fh.read() == got,
                   f"{image_id}: pooled bytes differ from serial")
    return {"requests": n, "device_launches": launches,
            "byte_identical": True}


# --- driver --------------------------------------------------------------

class CompileStats:
    """Compile seconds and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self) -> None:
        import jax

        self.seconds = 0.0
        self.events: dict = {}
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(
            self._duration)

    def _event(self, event: str, **_kw) -> None:
        if event.startswith("/jax/compilation_cache/"):
            key = event.rsplit("/", 1)[1]
            self.events[key] = self.events.get(key, 0) + 1

    def _duration(self, event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds

    def report(self) -> dict:
        return {"backend_compile_s": self.seconds,
                "cache_hits": self.events.get("cache_hits", 0),
                "cache_misses": self.events.get("cache_misses", 0)}


def _say(label: str, value) -> None:
    print(f"{label}: {json.dumps(value, default=str)}", flush=True)


async def _timed(name: str, coro, timings: dict):
    t0 = time.perf_counter()
    out = await coro
    timings[name] = time.perf_counter() - t0
    return out


def _tier1(stages: dict) -> str:
    """Name the Tier-1 that ran; raise if any chunk silently took the
    pure-Python coder (a missing or half-built native library)."""
    from bucketeer_tpu import native
    from bucketeer_tpu.codec import t1_batch

    _check(t1_batch.python_coded_blocks == 0,
           f"{t1_batch.python_coded_blocks} code-blocks took the "
           "pure-Python Tier-1 coder")
    _check(native.available(), "native Tier-1 library did not load")
    return "host native"


async def run_one_chip(workdir: str, seed: int, sizes: dict) -> dict:
    timings: dict = {}
    lossy = Server(workdir, "lossy",
                   **{"bucketeer.conversion.type": "lossy"})
    lossless = Server(workdir, "lossless",
                      **{"bucketeer.conversion.type": "lossless"})
    async with lossy, lossless:
        res = {"lossy": await _timed(
            "lossy", phase_lossy(lossy, seed, sizes["single"]), timings)}
        res["lossless"], src16, data16 = await _timed(
            "lossless", phase_lossless(lossless, seed, sizes["single"]),
            timings)
        res["csv"] = await _timed(
            "csv", phase_csv(lossy, seed, sizes["csv"], sizes["csv_items"]),
            timings)
        res["region"] = await _timed(
            "region", phase_region(lossless, src16, data16,
                                   sizes["region"]), timings)
        metrics = await lossy.metrics()
    res["smoke_timings_s"] = timings
    res["metrics"] = metrics
    return res


async def run_four_chips(workdir: str, seed: int, sizes: dict) -> dict:
    timings: dict = {}
    # The pool cap applies to a pool not yet spun up: build the
    # deployment before the first encode.
    pool = Server(workdir, "pool",
                  **{"bucketeer.conversion.type": "lossless",
                     "bucketeer.sched.devices": "4",
                     "bucketeer.sched.max.concurrent": str(sizes["pool_n"]),
                     "image.worker.instances": str(sizes["pool_n"])})
    async with pool:
        t0 = time.perf_counter()
        res = {"mesh": await asyncio.to_thread(
            phase_mesh, workdir, seed, sizes["mesh"],
            sizes.get("mesh_min_pixels"))}
        timings["mesh"] = time.perf_counter() - t0
        res["pool"] = await _timed(
            "pool", phase_pool(pool, seed, sizes["pool"], sizes["pool_n"]),
            timings)
        metrics = await pool.metrics()
    res["smoke_timings_s"] = timings
    res["metrics"] = metrics
    return res


FULL_SIZES = {"single": 4096, "csv": 2048, "csv_items": 4,
              "region": REGION, "mesh": 8192, "pool": 2048, "pool_n": 8}


def run(chips: int, seed: int, sizes: dict = FULL_SIZES) -> dict:
    """Run the phases for ``chips`` (1 or 4) in a temporary
    BUCKETEER_TMPDIR and print what they found; raises on any failed
    phase. Returns the device description for the last line."""
    import importlib.metadata as md

    import jax
    import jaxlib

    from bucketeer_tpu import native

    devices = jax.devices()
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    _say("versions", {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                      "libtpu": libtpu, "python": sys.version.split()[0]})
    _say("device", device)
    compiles = CompileStats()
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as workdir:
        os.environ["BUCKETEER_TMPDIR"] = workdir
        runner = run_one_chip if chips == 1 else run_four_chips
        res = asyncio.run(runner(workdir, seed, sizes))
    metrics = res.pop("metrics")
    stages = metrics.get("stages", {})
    counters = metrics.get("counters", {})
    _say("native_t1_loaded", native.available())
    for name in ("lossy", "lossless", "csv", "region", "mesh", "pool"):
        if name in res:
            _say(f"phase {name}", res[name])
    _say("tier1", _tier1(stages))
    _say("smoke timings, wall seconds (not a benchmark)",
         res["smoke_timings_s"])
    _say("stage seconds summed over the run (not a benchmark)",
         {k: v.get("total_s") for k, v in stages.items()})
    _say("compiles", {**compiles.report(),
                      "retrace": {k: v for k, v in counters.items()
                                  if k.startswith("retrace.")}})
    _say("encode.device_launches",
         {k: v for k, v in counters.items()
          if k.startswith("encode.device_launches")})
    _say("peak_bytes_in_use",
         [d.memory_stats()["peak_bytes_in_use"] if d.memory_stats()
          else None for d in devices])
    return device


def main(argv: list | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = parser.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r})", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    device = run(args.chips, args.seed)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
