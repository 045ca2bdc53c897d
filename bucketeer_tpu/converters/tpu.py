"""The in-process TPU converter — the component the reference outsources
to the Kakadu binary (reference: converters/KakaduConverter.java:55-77).

Emits the reference's full Kakadu recipe (reference:
KakaduConverter.java:38-44): ``Clevels=6 Clayers=6
Cprecincts={256,256},{256,256},{128,128} Stiles={512,512} Corder=RPCL
ORGgen_plt=yes ORGtparts=R Cblk={64,64} Cuse_sop=yes Cuse_eph=yes``;
lossless = reversible 5/3 + RCT (``Creversible=yes -rate -``), lossy =
irreversible 9/7 + ICT with PCRD-opt truncation to 3 bpp (``-rate 3``).
"""
from __future__ import annotations

import logging
import os
from pathlib import Path

from .. import obs
from ..codec import encoder as codec_encoder
from ..codec import tiff
from ..codec.encoder import EncodeParams, encode_jp2
from .base import Conversion, ConverterError, output_path

LOG = logging.getLogger(__name__)

LOSSY_RATE = 3.0    # reference: -rate 3 (KakaduConverter.java:43)

# Images at or above this pixel count route through the device mesh
# whenever more than one device is visible: a single giant tile is
# row-sharded (parallel.sharded_dwt), a tiled image's batches are
# data-sharded (parallel.batch.run_tiles_sharded). The default is sized
# so ordinary scans stay on the single-device overlapped pipeline and
# only archival monsters (BASELINE config 4's 400 MPix maps) pay the
# mesh dispatch overhead. Override: BUCKETEER_MESH_MIN_PIXELS env or
# the bucketeer.mesh.min.pixels config key (engine/batch.py).
DEFAULT_MESH_MIN_PIXELS = 64_000_000


def _env_mesh_min_pixels() -> int:
    return int(os.environ.get("BUCKETEER_MESH_MIN_PIXELS",
                              str(DEFAULT_MESH_MIN_PIXELS)))


# The persistent XLA compilation cache: JAX_COMPILATION_CACHE_DIR when
# the environment sets it (JAX reads that variable itself), else one
# fixed directory in the checkout. The path is part of what a later run
# must find again, so it never moves between runs.
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

_enabled_dir: str | None = None


def compile_cache_dir(environ=os.environ) -> str:
    """The directory the persistent compilation cache lives in."""
    return environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE_DIR)


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on at
    :func:`compile_cache_dir` (once per process) and return the
    directory. Every program is cached: the default thresholds skip
    fast compiles, but the encoder's many small per-shape programs are
    exactly the boot cost a warm cache removes."""
    global _enabled_dir
    if _enabled_dir is not None:
        return _enabled_dir
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    path = compile_cache_dir()
    os.makedirs(path, exist_ok=True)
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # The cache latches "initialized" on its first use; a compile that
    # ran before this call (backend probing) must not pin it off.
    compilation_cache.reset_cache()
    _enabled_dir = path
    return path


def compile_cache_entries() -> int:
    """Number of persisted XLA programs in the cache directory. Each
    program is a ``*-cache`` file (the ``*-atime`` twins are eviction
    bookkeeping, not entries)."""
    try:
        return sum(1 for e in os.scandir(compile_cache_dir())
                   if e.is_file() and e.name.endswith("-cache"))
    except OSError:
        return 0


class TpuConverter:
    """JPEG 2000 encoding on the local TPU/accelerator via the JAX codec."""

    name = "TPU"

    def __init__(self, lossy_rate: float = LOSSY_RATE,
                 jpx: bool = True,
                 mesh_min_pixels: int | None = None,
                 scheduler=None) -> None:
        self.lossy_rate = lossy_rate
        self.jpx = jpx
        self.mesh_min_pixels = (_env_mesh_min_pixels()
                                if mesh_min_pixels is None
                                else mesh_min_pixels)
        # Encodes go through the cross-request scheduler (admission
        # control + continuous device batching + shared host Tier-1).
        # None = the process-wide instance, resolved lazily per convert
        # (engine/scheduler.py imports converters back — a boot-time
        # import here would cycle).
        self.scheduler = scheduler
        enable_compile_cache()

    def _choose_mesh(self, h: int, w: int, params: EncodeParams):
        """Mesh routing for over-threshold images: a ('data', 'tile')
        mesh over all visible devices — all-spatial when the image is a
        single row-shardable tile, all-data otherwise. None keeps the
        single-device overlapped pipeline."""
        if self.mesh_min_pixels <= 0 or h * w < self.mesh_min_pixels:
            return None
        import jax

        from ..parallel.mesh import make_mesh
        from ..parallel.sharded_dwt import can_row_shard

        devices = jax.devices()
        if len(devices) < 2:
            return None
        if params.tile_size is None:
            # A single tile can only parallelize spatially. If its rows
            # don't shard, a data mesh would pad the batch of one up to
            # n_devices full-size zero tiles (parallel/batch.py) — all
            # host memory and dispatch overhead, zero speedup — so stay
            # on the single-device pipeline instead.
            if can_row_shard(h, params.levels, len(devices)):
                return make_mesh(devices, tile_parallel=len(devices))
            return None
        return make_mesh(devices, tile_parallel=1)

    def convert(self, image_id: str, source_path: str,
                conversion: Conversion = Conversion.LOSSLESS,
                priority: int | None = None,
                deadline_s: float | None = None) -> str:
        """Convert one source image to a JP2/JPX derivative.

        ``priority``: scheduler queue class — engine/scheduler.py
        PRIORITY_SINGLE (default, interactive requests) or
        PRIORITY_BATCH (CSV items; the batch worker passes it so
        interactive traffic jumps the queue). ``deadline_s`` bounds the
        request end to end; expiry raises through as a typed scheduler
        error. Raises ``QueueFull`` (503 + Retry-After upstream) when
        the scheduler's bounded queue is at depth.
        """
        from ..engine import scheduler as sched_mod

        if not os.path.exists(source_path):
            raise ConverterError(f"source not found: {source_path}")
        try:
            with obs.stage(codec_encoder._metrics_sink,
                           "convert.tiff_read") as st:
                img, bitdepth = tiff.read_image(source_path)
                st.pixels = img.shape[0] * img.shape[1]
        except Exception as exc:
            raise ConverterError(
                f"cannot read {source_path}: {exc}") from exc

        h, w = img.shape[:2]
        params = EncodeParams.kakadu_recipe(
            lossless=conversion == Conversion.LOSSLESS,
            rate=self.lossy_rate)
        # Tiny images can't sustain 6 levels; clamp like encoders do.
        while params.levels > 1 and (min(h, w) >> params.levels) < 4:
            params.levels -= 1
        if max(h, w) <= params.tile_size:
            params.tile_size = None         # single tile, like kdu untiled
        # The base step is calibrated for 8-bit signals; scale it with
        # the signal range so deeper scans quantize proportionally.
        params.base_delta *= (1 << (bitdepth - 8))
        mesh = self._choose_mesh(h, w, params)
        if mesh is not None:
            LOG.info("routing %s (%dx%d) through the device mesh %s",
                     image_id, w, h, dict(mesh.shape))
        sched = self.scheduler or sched_mod.get_scheduler()
        try:
            with obs.span("convert.encode", image_id=image_id,
                          pixels=h * w):
                data = sched.encode_jp2(
                    img, bitdepth, params, jpx=self.jpx, mesh=mesh,
                    priority=(sched_mod.PRIORITY_SINGLE
                              if priority is None else priority),
                    deadline_s=deadline_s)
        except (sched_mod.QueueFull, sched_mod.DeadlineExceeded):
            # Admission/deadline outcomes are protocol, not converter
            # failures: the HTTP layer maps them to 503 + Retry-After.
            raise
        except Exception as exc:
            raise ConverterError(
                f"encode failed for {image_id}: {exc}") from exc

        dest = output_path(image_id, ".jpx" if self.jpx else ".jp2")
        # Unique temp name: concurrent converts of the same id must not
        # interleave writes before the atomic replace.
        tmp = f"{dest}.{os.getpid()}.{id(data):x}.part"
        with open(tmp, "wb") as fh:
            fh.write(data)
        os.replace(tmp, dest)
        return dest
