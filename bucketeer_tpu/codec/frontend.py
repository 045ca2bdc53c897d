"""Device-side EBCOT Tier-1 front-end: bit-plane decomposition, coding
statistics and payload compaction on the TPU.

This stage exists because the encoder's measured ceiling was the
device-to-host transfer of raw int32 Mallat planes (4 bytes/sample —
~90% of wall clock over a constrained PCIe/tunnel link), while the MQ
coder itself only ever consumes *bit-planes*. So the device now:

1. runs the fused sample transform (pipeline._transform_batch: level
   shift + RCT/ICT + DWT + quantization),
2. carves the Mallat planes into 64x64 code-blocks (the reference
   recipe's ``Cblk={64,64}``, converters/KakaduConverter.java:38-44),
3. computes per-block/per-plane Tier-1 statistics — newly-significant
   counts and *exact* distortion sums (they replace the fractional-bit
   planes the host coder used for PCRD slopes), and
4. packs each bit-plane and the sign plane into 512-byte bitmaps held
   device-side; a gather then compacts exactly the planes the rate
   target needs (descending from each block's MSB to its floor) before
   the one device->host copy.

A block with b coded planes ships ``(b+1) * 512`` bytes instead of
``4096 * 4`` — typically 8-20x less, and blocks the rate allocator will
discard ship nothing at all. The host C++ coder (native/t1.cpp,
``t1_encode_packed``) consumes the bitmaps directly.

Everything here is plain jnp on static shapes, so the same program runs
on TPU and on the CPU backend (no-TPU dev mode / tests).
"""
from __future__ import annotations

import threading
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis import graftcost, retrace
from ..analysis.contracts import contract
from .pipeline import (TilePlan, _bucket, _step_map, _transform_batch,
                       donate_argnums_if_supported)
from .quant import FRAC_BITS

CBLK = 64
ROW_BYTES = 512          # one packed 64x64 bitmap


@dataclass(frozen=True)
class BlockMeta:
    """One code-block's place inside a tile (canonical frontend order)."""
    comp: int
    slot_i: int          # index into plan.slots
    iy: int              # cell raster position within the tile-band
    ix: int
    h: int               # true coded extent (<= 64)
    w: int


@dataclass(frozen=True)
class FrontendLayout:
    """Host-side mirror of the device blockification for one plan."""
    plan: TilePlan
    metas: tuple          # tuple[BlockMeta], length n_per_tile
    P: int                # plane capacity (max Mb over subbands)
    mb_caps: tuple        # per-meta subband Mb (guard-bit ceiling)

    @property
    def n_per_tile(self) -> int:
        return len(self.metas)


@lru_cache(maxsize=256)
def layout_for(plan: TilePlan) -> FrontendLayout:
    """Block order: component-major, then plan.slots order (resolution
    then LL/HL/LH/HH), then raster cells — matching the band/cell walk
    of encoder._tile_bands so host metadata lines up index-for-index
    with the device's concatenated block axis."""
    metas = []
    caps = []
    for c in range(plan.n_comps):
        for si, s in enumerate(plan.slots):
            nby = -(-s.h // CBLK) if s.h else 0
            nbx = -(-s.w // CBLK) if s.w else 0
            for iy in range(nby):
                for ix in range(nbx):
                    metas.append(BlockMeta(
                        c, si, iy, ix,
                        min(CBLK, s.h - iy * CBLK),
                        min(CBLK, s.w - ix * CBLK)))
                    caps.append(s.quant.n_bitplanes)
    P = max((s.quant.n_bitplanes for s in plan.slots), default=1)
    return FrontendLayout(plan, tuple(metas), P, tuple(caps))


def _blockify(planes: jnp.ndarray, plan: TilePlan) -> jnp.ndarray:
    """(B, C, H, W) Mallat planes -> (B * n_per_tile, 64, 64) int32 in
    layout_for order. Partial edge blocks sit at the top-left of their
    64x64 container, zero-padded (padding never creates significance)."""
    b = planes.shape[0]
    parts = []
    for c in range(plan.n_comps):
        for s in plan.slots:
            if s.h == 0 or s.w == 0:
                continue
            band = planes[:, c, s.y0:s.y0 + s.h, s.x0:s.x0 + s.w]
            nby, nbx = -(-s.h // CBLK), -(-s.w // CBLK)
            band = jnp.pad(band, ((0, 0), (0, nby * CBLK - s.h),
                                  (0, nbx * CBLK - s.w)))
            band = band.reshape(b, nby, CBLK, nbx, CBLK)
            parts.append(band.transpose(0, 1, 3, 2, 4).reshape(
                b, nby * nbx, CBLK, CBLK))
    return jnp.concatenate(parts, axis=1).reshape(-1, CBLK, CBLK)


def _pack_bits(bits: jnp.ndarray) -> jnp.ndarray:
    """(N, 64, 64) {0,1} -> (N, 512) uint8, LSB-first within each byte
    (sample (y, x) -> byte y*8 + x//8, bit x%8)."""
    n = bits.shape[0]
    b = bits.reshape(n, CBLK, 8, 8).astype(jnp.int32)
    w = (1 << jnp.arange(8, dtype=jnp.int32))
    return (b * w).sum(axis=-1).astype(jnp.uint8).reshape(n, ROW_BYTES)


def _frontend_body(plan: TilePlan, P: int, frac_bits: int, step_map,
                   batch: jnp.ndarray):
    """The full device program for one tile batch: per-plane bitmaps
    for the host coder's packed path, plus the per-block statistics."""
    planes = _transform_batch(plan, step_map, batch)
    blocks = _blockify(planes, plan)
    mag_fp = jnp.abs(blocks)
    idx = (mag_fp >> frac_bits).astype(jnp.uint32)
    maxidx = idx.max(axis=(1, 2)).astype(jnp.int32)

    rows = [_pack_bits(blocks < 0)]          # sign plane first
    for p in range(P):
        rows.append(_pack_bits((idx >> p) & 1))
    rows = jnp.stack(rows, axis=1)           # (N, P+1, 512)

    if frac_bits:
        tv = mag_fp.astype(jnp.float32) * (1.0 / (1 << frac_bits))
    else:
        tv = mag_fp.astype(jnp.float32)
    newsig, sigd, refd = [], [], []
    for p in range(P):
        hi = (idx >> p).astype(jnp.int32)
        is_new = (hi != 0) & ((idx >> (p + 1)) == 0)
        already = (idx >> (p + 1)) != 0
        newsig.append(is_new.sum(axis=(1, 2), dtype=jnp.int32))
        # Significance at plane p reconstructs to 1.5 * 2^p. Expanded,
        # cancellation-free form: tv² - (tv-r)² computed directly loses
        # float32 precision for high-Mb content (tv ~ 2^18 gives ~1%
        # per-sample error), and these sums replace the host's exact
        # distortions for PCRD slope ranking.
        r = jnp.float32(1.5 * (1 << p))
        sd = jnp.where(is_new, r * (2.0 * tv - r), 0.0)
        sigd.append(sd.sum(axis=(1, 2), dtype=jnp.float32))
        # Refinement halves the uncertainty interval (t1.ref_dist).
        # (tv-r1)² - (tv-r0)² in expanded form for the same reason.
        v1 = ((idx >> (p + 1)) << (p + 1)).astype(jnp.float32)
        v0 = ((idx >> p) << p).astype(jnp.float32)
        r1 = v1 + jnp.float32(1 << p)
        r0 = v0 + jnp.float32(0.5 * (1 << p))
        rd = jnp.where(already, (r0 - r1) * (2.0 * tv - r0 - r1), 0.0)
        refd.append(rd.sum(axis=(1, 2), dtype=jnp.float32))
    stats = (maxidx, jnp.stack(newsig, 1), jnp.stack(sigd, 1),
             jnp.stack(refd, 1))
    return rows.reshape(-1, ROW_BYTES), stats


def frontend_program(plan: TilePlan, P: int):
    """(traceable fn, device donate_argnums) for the front-end — the
    exact construction :func:`_compiled_frontend` jits, shared with
    the device audit (analysis/deviceaudit.py) so the audited artifact
    is the shipped one.

    The donate spec is empty by *verified fact*, not oversight: the
    staged (B, h, w, C) int32 tile batch matches no output aval (rows
    are uint8 bitmaps, stats are per-block vectors), so XLA silently
    drops the alias — the audit lowers this program with donation
    forced and proves the ``tf.aliasing_output`` attribute never
    appears. Requesting it anyway would only emit a per-compile
    warning; ``rules_donation.WHITELIST`` records the same fact."""
    frac_bits = 0 if plan.lossless else FRAC_BITS
    step_map = jnp.asarray(_step_map(plan)) if not plan.lossless else None
    fn = retrace.instrument(
        "frontend", partial(_frontend_body, plan, P, frac_bits, step_map))
    return fn, ()


@lru_cache(maxsize=256)
def _compiled_frontend(plan: TilePlan, P: int):
    fn, donate = frontend_program(plan, P)
    return jax.jit(fn, donate_argnums=donate_argnums_if_supported(*donate))


@dataclass
class FrontendResult:
    """Per tile-batch device output. ``rows`` stays on device until
    fetch_payload pulls the compacted subset.

    ``block_base``: first block's index within the shared device
    ``rows`` array. Non-zero when this result is one request's window
    onto a merged cross-request launch (engine/scheduler.py) — the
    per-block host arrays are already sliced, only the row gather needs
    the offset (fetch_payload applies it)."""
    layout: FrontendLayout
    n_tiles: int          # real (unpadded) tiles in the batch
    rows: object          # jax array (B*n_per_tile*(P+1), 512) uint8
    nbps: np.ndarray      # (n_blocks,) int32 — real blocks only
    newsig: np.ndarray    # (n_blocks, P) int32
    sigd: np.ndarray      # (n_blocks, P) float32
    refd: np.ndarray      # (n_blocks, P) float32
    block_base: int = 0   # offset into the shared rows array (blocks)

    @property
    def n_blocks(self) -> int:
        return self.n_tiles * self.layout.n_per_tile


@dataclass
class PendingFrontend:
    """A dispatched, asynchronously executing frontend batch.

    ``dispatch_frontend`` returns immediately after queueing the device
    program (JAX dispatch is async); :meth:`resolve` blocks only for the
    small stats transfer. This is the seam the encoder's overlapped
    pipeline uses: chunk N+1's device program runs while chunk N's
    packed payload is Tier-1 coded on host threads."""
    layout: FrontendLayout
    n_tiles: int
    rows: object          # device array, stays in HBM
    stats: object         # device array tuple (maxidx, newsig, sigd, refd)
    # Host copy of ``stats``, fetched once: a merged cross-request
    # launch (engine/scheduler.py) is resolved by several request
    # threads, each slicing its own window.
    _stats_np: object = None
    _stats_lock: object = field(default_factory=threading.Lock,
                                repr=False)

    def _host_stats(self):
        with self._stats_lock:
            if self._stats_np is None:
                self._stats_np = jax.device_get(self.stats)
        return self._stats_np

    def resolve_stats(self, tile_off: int = 0,
                      n_tiles: int | None = None) -> FrontendResult:
        """Block for the per-block stats (a few KB) and build the
        FrontendResult. The bitmap rows stay on device.

        ``tile_off``/``n_tiles`` window the result onto a contiguous
        tile range of the batch — the seam the cross-request scheduler
        uses to hand each request its share of a merged launch. The
        defaults resolve the whole batch (solo launches)."""
        maxidx, newsig, sigd, refd = self._host_stats()
        if n_tiles is None:
            n_tiles = self.n_tiles
        npt = self.layout.n_per_tile
        off = tile_off * npt
        sl = slice(off, off + n_tiles * npt)
        m = maxidx[sl]
        nbps = np.zeros(n_tiles * npt, dtype=np.int32)
        nz = m > 0
        nbps[nz] = np.floor(np.log2(
            m[nz].astype(np.float64))).astype(np.int32) + 1
        # Guard-bit invariant: a magnitude above 2^Mb would make
        # payload_plan emit row indices into the next block's rows, and
        # the clamped device gather would corrupt the codestream
        # *silently*. Fail loudly like the legacy host path — a real
        # exception, not an assert, so `python -O` can't strip it.
        caps = np.tile(np.asarray(self.layout.mb_caps, dtype=np.int32),
                       n_tiles)
        bad = nbps > caps
        if bad.any():
            raise ValueError(
                f"guard-bit violation: block nbps {nbps[bad].max()} "
                f"exceeds its subband Mb "
                f"{caps[bad][int(np.argmax(nbps[bad]))]} (coefficient "
                "overflow in the device front-end)")
        return FrontendResult(self.layout, n_tiles, self.rows, nbps,
                              newsig[sl], sigd[sl], refd[sl],
                              block_base=off)


@contract(shapes={"tiles": [("B", "h", "w"), ("B", "h", "w", "C")]},
          dtypes={"tiles": "number"})
def dispatch_frontend(plan: TilePlan, tiles: np.ndarray,
                      device=None, pool=()) -> PendingFrontend:
    """Queue transform + blockify + stats for a (B, h, w[, C]) tile
    batch on the device and return without waiting for the result.
    ``device`` (a ``jax.Device``) stages the batch with a *committed*
    ``jax.device_put`` so the program — and the payload gather consuming its
    output, even from another thread — runs on that core; None keeps default
    placement. ``pool``: the devices of the scheduler's pool that ``device``
    belongs to; on a pool of more than one, the first launch of a shape
    readies its programs on all of them (:func:`_ready_on_pool`)."""
    if tiles.ndim == 3:
        tiles = tiles[..., None]
    # Dtype audit at the host->device boundary: the device program's
    # first op widens to int32/float32 anyway (pipeline._transform_batch),
    # so an 8-byte host dtype would double or quadruple the transfer for
    # nothing. Narrow before staging.
    if tiles.dtype == np.int64:
        tiles = tiles.astype(np.int32)
    elif tiles.dtype == np.float64:
        tiles = tiles.astype(np.float32)
    b = tiles.shape[0]
    pad = _bucket(b) - b
    # Workload-shape seam: graftcost weighs per-bucket padding waste by
    # what the service actually launched (docs/analysis.md, graftcost).
    graftcost.record_bucket("frontend.batch", b, b + pad)
    if pad:
        tiles = np.concatenate(
            [tiles, np.zeros((pad,) + tiles.shape[1:], tiles.dtype)])
    layout = layout_for(plan)
    # Committed placement (device_put) vs jnp.asarray matters: an
    # uncommitted array snaps back to the default device the moment a
    # different thread consumes it; a committed one pins the downstream
    # payload gather to the pool worker's core.
    staged = (jax.device_put(tiles, device) if device is not None
              else jnp.asarray(tiles))
    rows, stats = _compiled_frontend(plan, layout.P)(staged)
    if len(pool) > 1:
        _ready_on_pool(pool, device, plan, layout.P, staged, rows)
    return PendingFrontend(layout, b, rows, stats)


# (program shape, device) pairs already made ready by _ready_on_pool:
# process-wide, as JAX's cache of compiled programs is.
_POOL_READY: set = set()
_POOL_READY_LOCK = threading.Lock()


def _ready_on_pool(pool, device, plan: TilePlan, P: int, staged,
                   rows) -> None:
    """Compile, or load from the persistent cache, the programs of a
    launch's shape on every device of the pool, once per shape and
    device, before the launch returns: the front-end on the devices
    other than ``device`` (whose call just did it), and the payload
    gather over the launch's ``rows`` on all of them. A compiled
    program is keyed by its device assignment, so without this a shape
    warmed on one device compiles again the first time another runs
    it, which the retrace counters (they count traces) do not show.
    The lowerings match the calls exactly: committed inputs carry the
    device's sharding, the gather's uncommitted index none."""
    key = (plan, P, staged.shape, staged.dtype)
    with _POOL_READY_LOCK:
        todo = [d for d in pool if (key, d) not in _POOL_READY]
        jobs = []
        for d in todo:
            on_d = jax.sharding.SingleDeviceSharding(d)
            if d != device:
                jobs.append((_compiled_frontend(plan, P),
                             (jax.ShapeDtypeStruct(staged.shape,
                                                   staged.dtype,
                                                   sharding=on_d),)))
            jobs.append((_compiled_gather(GATHER_CHUNK),
                         (jax.ShapeDtypeStruct(rows.shape, rows.dtype,
                                               sharding=on_d),
                          jax.ShapeDtypeStruct(
                              (GATHER_CHUNK,),
                              jax.dtypes.canonicalize_dtype(np.int64)))))
        if jobs:
            # One thread per program: XLA compiles outside the GIL.
            with ThreadPoolExecutor(len(jobs)) as ex:
                list(ex.map(lambda job: job[0].lower(*job[1]).compile(),
                            jobs))
        _POOL_READY.update((key, d) for d in todo)


@contract(shapes={"tiles": [("B", "h", "w"), ("B", "h", "w", "C")]},
          dtypes={"tiles": "number"})
def run_frontend(plan: TilePlan, tiles: np.ndarray) -> FrontendResult:
    """Transform + blockify + stats for a (B, h, w[, C]) tile batch,
    blocking until the stats are on host (the packed bitmap rows stay on
    device). Synchronous wrapper over dispatch_frontend/resolve_stats."""
    return dispatch_frontend(plan, tiles).resolve_stats()


def gather_program():
    """(traceable fn, donate spec) for the compaction gather — audit
    seam. ``rows`` is deliberately non-donated (whitelisted): one
    payload fetch re-reads the same device buffer across chunks."""
    def gather(rows, src):
        return rows[src]
    return retrace.instrument("gather", gather), ()


@lru_cache(maxsize=8)
def _compiled_gather(chunk_rows: int):
    fn, donate = gather_program()
    return jax.jit(fn, donate_argnums=donate_argnums_if_supported(*donate))


GATHER_CHUNK = 4096      # rows per gather dispatch (= 2 MB of payload)


def payload_plan(nbps: np.ndarray, floors: np.ndarray, P: int):
    """Row indices to fetch: for each live block (nbp > floor), its sign
    row then plane rows nbp-1 .. floor (coding order). Returns
    (src int64 (R,), offsets int64 (n+1,)) — offsets in rows, so block
    b's payload is rows [offsets[b], offsets[b+1])."""
    n = len(nbps)
    # nbps beyond the packed plane capacity would index into the *next*
    # block's rows; the device gather clamps out-of-bounds indices, so
    # the corruption would be silent. Fail loudly (ADVICE round 5 #1) —
    # a real exception, not an assert, so `python -O` can't strip it.
    if n and int(nbps.max()) > P:
        raise ValueError(
            f"block nbps {int(nbps.max())} exceeds packed plane "
            f"capacity {P}: guard-bit invariant violated upstream")
    counts = np.where(nbps > floors, nbps - floors + 1, 0).astype(np.int64)
    offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(counts, out=offsets[1:])
    src = np.empty(int(offsets[-1]), dtype=np.int64)
    base = np.arange(n, dtype=np.int64) * (P + 1)
    live = np.nonzero(counts)[0]
    for b in live:
        o = offsets[b]
        src[o] = base[b]                       # sign row
        nplanes = counts[b] - 1
        src[o + 1:o + 1 + nplanes] = (
            base[b] + 1 + np.arange(nbps[b] - 1, floors[b] - 1, -1))
    return src, offsets


def gather_rows(rows, src: np.ndarray, row_bytes: int) -> np.ndarray:
    """Compact selected rows of a device (R_total, row_bytes) uint8
    array and copy them host-side in fixed-size gather chunks (one
    compiled program per row width, bounded padding). Shared by the
    packed-bitmap payload fetch and the CX/D symbol-stream fetch."""
    r = len(src)
    if r == 0:
        return np.empty((0, row_bytes), dtype=np.uint8)
    padded = -(-r // GATHER_CHUNK) * GATHER_CHUNK
    src_pad = np.zeros(padded, dtype=np.int64)
    src_pad[:r] = src
    gather = _compiled_gather(GATHER_CHUNK)
    outs = []
    for i in range(0, padded, GATHER_CHUNK):
        outs.append(gather(rows, jnp.asarray(src_pad[i:i + GATHER_CHUNK])))
    out = np.concatenate([np.asarray(jax.device_get(o)) for o in outs])
    return out[:r]


@contract(shapes={"src": ("R",)}, dtypes={"src": "integer"})
def fetch_payload(result: FrontendResult, src: np.ndarray) -> np.ndarray:
    """Compact the selected bitmap rows on device and copy them host-side.
    Returns (R, 512) uint8. ``src`` is relative to the result's own
    first block (payload_plan output); for a window onto a merged
    cross-request launch the shared-array offset is applied here."""
    if result.block_base:
        src = src + np.int64(result.block_base) * (result.layout.P + 1)
    return gather_rows(result.rows, src, ROW_BYTES)


def unpack_block(payload: np.ndarray, offset: int, nbp: int, floor: int,
                 h: int, w: int):
    """Numpy reference unpack (also the no-native fallback): payload rows
    for one block -> (mags uint32 (h,w), negs bool (h,w)). Bits below
    ``floor`` are zero — the coder never visits those planes."""
    def bits(row):
        return np.unpackbits(row.reshape(CBLK, 8), axis=1,
                             bitorder="little")[:h, :w]
    negs = bits(payload[offset]).astype(bool)
    mags = np.zeros((h, w), dtype=np.uint32)
    for j, p in enumerate(range(nbp - 1, floor - 1, -1)):
        mags |= bits(payload[offset + 1 + j]).astype(np.uint32) << p
    return mags, negs
