"""Compressed-domain coefficient delivery: stop the decode after
Tier-1 + dequantization and hand the caller device-resident per-subband
coefficient tensors.

"RGB no more" (PAPERS.md) feeds vision models minimally decoded
transform coefficients instead of pixels; this module is that read
path for our codestreams. :func:`decode_to_coefficients` runs Tier-2
parsing and host Tier-1 exactly like ``decode()`` and then *stops*: no
inverse DWT, no inverse color transform, no level shift. The decoded
half-magnitudes dequantize in one tiny jitted device program
(:func:`dequant_program`) whose outputs are returned as **device
arrays** — a training job consumes them with zero host round-trip, and
composing with the PR 6 StreamIndex makes ``region=`` reads a sharded,
random-access coefficient input pipeline.

Subband layout contract (the shape tests pin):

- bands are keyed ``(res, name)``: ``(0, "LL")`` plus
  ``(r, "HL"/"LH"/"HH")`` for ``r = 1 .. levels - reduce``;
- each band is one ``(C, H_b, W_b)`` plane assembled across the tile
  grid: tile ``(ty, tx)``'s band rectangle sits at the prefix-sum
  origin of the preceding tiles' band extents (per-tile DWT means the
  global plane is a grid of per-tile bands, not one whole-image
  transform — documented, deterministic, and exactly what "slicing the
  subband state out of a full decode" produces);
- values are exact coefficients: reversible streams give int32
  ``sign * (|hval| >> 1)``, irreversible float32 ``hval * delta_b/2``
  (the decode inverse's own dequantization, stopped early);
- ``region=(x, y, w, h)`` (full-resolution reference-grid coords) maps
  through ``reduce`` to the sample window and then per band through
  the band's dyadic factor ``d`` (``d = level`` for detail bands,
  ``levels - reduce`` for LL) as
  ``[w0 >> d, ceil(w1 / 2^d))`` clamped to the band — the exact crop
  of the full coefficient read the parity tests assert, with Tier-1
  running only for code-blocks intersecting those windows.
"""
from __future__ import annotations

import struct
import threading
from contextlib import contextmanager
from dataclasses import dataclass
from functools import lru_cache

import numpy as np

from .. import obs
from ..analysis import retrace
from ..codec.decode import decoder as decoder_mod
from ..codec.decode import index as sindex
from ..codec.decode import parser
from ..codec.decode.errors import DecodeError, InvalidParam
from ..codec.encoder import _ceil_div
from ..codec.pipeline import _band_geometry, donate_argnums_if_supported


def band_keys(levels: int) -> list:
    """Canonical band order: LL first, then resolutions coarse to fine,
    HL/LH/HH within each — the order the dequant program's inputs and
    every ``bands`` dict iterate in."""
    return [(0, "LL")] + [(r, n) for r in range(1, levels + 1)
                          for n in ("HL", "LH", "HH")]


def band_downsample(res: int, levels: int) -> int:
    """log2 of the band's dyadic subsampling relative to the reduced
    sample grid: LL is ``levels`` deep, the detail bands of resolution
    ``r`` sit at level ``levels - r + 1``."""
    return levels if res == 0 else levels - res + 1


def band_window(w0: int, w1: int, d: int, extent: int) -> tuple:
    """Map a sample window edge pair through a band's dyadic factor:
    ``[w0 >> d, ceil(w1 / 2^d))`` clamped to the band extent — the
    subband-slicing rule of the module contract."""
    a = min(w0 >> d, extent)
    b = min(_ceil_div(w1, 1 << d), extent)
    return a, max(a, b)


@dataclass
class CoefficientSet:
    """The product of :func:`decode_to_coefficients`: device-resident
    per-subband coefficient planes plus the geometry to interpret
    them. ``windows`` is None for full reads; for region reads it maps
    each band to the ``(y0, y1, x0, x1)`` rectangle of the global band
    plane the returned array covers."""
    width: int
    height: int
    n_comps: int
    bitdepth: int
    levels: int              # levels remaining after ``reduce``
    reduce: int
    reversible: bool
    used_mct: bool
    bands: dict              # (res, name) -> jax array (C, H_b, W_b)
    deltas: dict             # (res, name) -> signaled quantizer step
    region: tuple | None = None
    windows: dict | None = None

    @property
    def nbytes(self) -> int:
        return sum(int(a.nbytes) for a in self.bands.values())

    def to_host(self) -> dict:
        """Materialize every band on host — the set's one sanctioned
        device->host seam (rules_jax.D2H_SANCTIONED); in-process
        consumers feed the device arrays onward instead."""
        import jax

        return {key: np.asarray(jax.device_get(
                    arr.materialize() if isinstance(arr, BandSlice)
                    else arr))
                for key, arr in self.bands.items()}


# --- scheduler seam -------------------------------------------------------

_TLS = threading.local()


@contextmanager
def coeff_services(check=None, launch=None):
    """Install per-thread hooks for the duration of a coefficient read
    — the coefficient analog of ``tensor_services``:

    - ``check()`` is polled at per-tile Tier-1 boundaries (the
      scheduler's deadline hook for ``kind="batchread"`` jobs);
    - ``launch(reversible, deltas, arrays)`` replaces the inline
      dequant dispatch, so the scheduler can queue the
      ``decode.coeffs.dequant`` launch on the device pool where
      compatible launches from concurrent batch items merge into one
      combined device program (engine/scheduler.py
      ``dispatch_dequant``). Must return the same tuple of per-band
      device arrays the inline path produces.
    """
    prev = (getattr(_TLS, "check", None), getattr(_TLS, "launch", None))
    _TLS.check, _TLS.launch = check, launch
    try:
        yield
    finally:
        _TLS.check, _TLS.launch = prev


def _poll() -> None:
    check = getattr(_TLS, "check", None)
    if check is not None:
        check()


def current_services() -> tuple:
    """The calling thread's installed ``(check, launch)`` hooks, or
    ``(None, None)``. The batch assembler reads these on the admitted
    request thread and re-installs them (with the fan-out width bound)
    in each of its item worker threads — thread-locals don't cross the
    fan-out otherwise."""
    return (getattr(_TLS, "check", None),
            getattr(_TLS, "launch", None))


# --- the jitted dequant back half ----------------------------------------

def dequant_program(reversible: bool, deltas: tuple):
    """(traceable fn, device donate_argnums) for the coefficient
    dequantizer — audit seam (analysis/deviceaudit.py). Input: the
    tuple of per-band (C, H_b, W_b) int32 half-magnitude planes;
    output: the dequantized coefficient planes, same shapes. The
    staged input is donated on the reversible path (int32 -> int32,
    the audit verifies XLA aliases every band buffer); irreversibly
    the outputs are float32 and XLA drops the alias (no output matches
    an input aval), so the spec is empty by verified fact."""
    import jax.numpy as jnp

    def body(*hvs):
        out = []
        for hv, delta in zip(hvs, deltas):
            if reversible:
                mag = jnp.abs(hv) >> 1
                out.append(jnp.where(hv < 0, -mag, mag))
            else:
                out.append(hv.astype(jnp.float32)
                           * jnp.float32(delta * 0.5))
        return tuple(out)

    # One top-level arg per band so the declared donate spec equals
    # the lowered alias set index for index (the audit's invariant).
    donate = tuple(range(len(deltas))) if reversible else ()
    return retrace.instrument("coeff_dequant", body), donate


@lru_cache(maxsize=256)
def _compiled_dequant(reversible: bool, deltas: tuple):
    import jax

    fn, donate = dequant_program(reversible, deltas)
    return jax.jit(fn, donate_argnums=donate_argnums_if_supported(*donate))


class BandSlice:
    """One image's row of a merged batched-dequant output: a lazy view
    ``parent[index]`` the scheduler's combined launch hands back to
    each fanned-out item instead of paying a device slice dispatch per
    band per image. The batch assembler recognizes sibling views of
    one parent and gathers the whole batch in a single fused program;
    any other consumer materializes transparently via
    :func:`numpy.asarray`."""

    __slots__ = ("parent", "index")

    def __init__(self, parent, index: int):
        self.parent = parent
        self.index = index

    @property
    def shape(self):
        return self.parent.shape[1:]

    @property
    def dtype(self):
        return self.parent.dtype

    def materialize(self):
        return self.parent[self.index]

    def __array__(self, dtype=None):
        arr = np.asarray(self.materialize())
        return arr if dtype is None else arr.astype(dtype)


def _run_dequant(reversible: bool, deltas: tuple, arrays: list):
    launch = getattr(_TLS, "launch", None)
    if launch is not None:
        return launch(reversible, deltas, arrays)
    return run_dequant_inline(reversible, deltas, arrays)


def run_dequant_inline(reversible: bool, deltas: tuple, arrays: list,
                       device=None):
    """Dispatch the compiled dequantizer directly (bypassing any
    installed ``coeff_services`` launch hook): the scheduler's merged
    device launch calls this with the per-image planes stacked along a
    leading batch axis — the program is elementwise per band, so the
    batched outputs slice back per image bit-exactly."""
    import jax.numpy as jnp

    fn = _compiled_dequant(reversible, deltas)
    if device is not None:
        import jax

        return fn(*(jax.device_put(np.asarray(a), device)
                    for a in arrays))
    return fn(*(jnp.asarray(a) for a in arrays))


# --- geometry helpers -----------------------------------------------------

def _tile_grid(ps: parser.ParsedStream) -> tuple:
    return (_ceil_div(ps.height, ps.tile_h),
            _ceil_div(ps.width, ps.tile_w))


def _band_dims(rh: int, rw: int, levels: int) -> dict:
    """(res, name) -> (y0, x0, bh, bw) of the tile-local Mallat layout
    (offsets index the tile's (C, rh, rw) half-magnitude planes)."""
    out = {}
    for name, lvl, y0, x0, bh, bw in _band_geometry(rh, rw, levels):
        res = 0 if name == "LL" else levels - lvl + 1
        out[(res, name)] = (y0, x0, bh, bw)
    return out


def _grid_extents(ps: parser.ParsedStream, reduce: int,
                  levels: int) -> tuple:
    """Per-band global assembly geometry: ({key: (row_offsets,
    col_offsets)}, {key: (H, W)}) where offsets are the prefix sums of
    per-tile-row / per-tile-column band extents."""
    n_ty, n_tx = _tile_grid(ps)
    row_h = [_ceil_div(min(ps.tile_h, ps.height - ty * ps.tile_h),
                       1 << reduce) for ty in range(n_ty)]
    col_w = [_ceil_div(min(ps.tile_w, ps.width - tx * ps.tile_w),
                       1 << reduce) for tx in range(n_tx)]
    offs, dims = {}, {}
    for key in band_keys(levels):
        roffs, total_h = [0], 0
        for rh in row_h:
            bd = _band_dims(rh, col_w[0], levels)[key]
            total_h += bd[2]
            roffs.append(total_h)
        coffs, total_w = [0], 0
        for cw in col_w:
            bd = _band_dims(row_h[0], cw, levels)[key]
            total_w += bd[3]
            coffs.append(total_w)
        offs[key] = (roffs, coffs)
        dims[key] = (total_h, total_w)
    return offs, dims


@dataclass
class _CoeffPlan:
    """Quacks like device.RegionPlan for the Tier-1 window fill
    (decoder._tile_region_hvals consumes ``slots`` only): per-band
    window rectangles in band coordinates, *without* the DWT halo — no
    synthesis runs, so no halo is owed."""
    slots: tuple


# --- the public entry -----------------------------------------------------

def _full_impl(data: bytes, reduce: int, layers) -> CoefficientSet:
    with obs.stage(decoder_mod._metrics_sink, "decode.t2_parse") as st:
        ps = parser.parse(data, reduce=reduce, layers=layers)
        st.items = ps.n_packets
    levels = ps.levels - reduce
    offs, dims = _grid_extents(ps, reduce, levels)
    keys = band_keys(levels)
    planes = {key: np.zeros((ps.n_comps,) + dims[key], dtype=np.int32)
              for key in keys}

    n_tx = _tile_grid(ps)[1]
    n_blocks = n_dec = 0
    for tile in ps.tiles:
        _poll()
        hv, nb, nd = decoder_mod._tile_hvals(ps, tile, reduce)
        n_blocks += nb
        n_dec += nd
        ty, tx = divmod(tile.idx, n_tx)
        rh, rw = hv.shape[1:]
        bd = _band_dims(rh, rw, levels)
        for key in keys:
            y0, x0, bh, bw = bd[key]
            roffs, coffs = offs[key]
            planes[key][:, roffs[ty]:roffs[ty] + bh,
                        coffs[tx]:coffs[tx] + bw] = \
                hv[:, y0:y0 + bh, x0:x0 + bw]

    deltas = {key: float(ps.quants[key].delta) for key in keys}
    with obs.stage(decoder_mod._metrics_sink, "decode.coeff_dequant"):
        out = _run_dequant(ps.reversible,
                           tuple(deltas[k] for k in keys),
                           [planes[k] for k in keys])
    _count(ps, n_blocks, n_dec, region=False)
    return CoefficientSet(
        ps.width, ps.height, ps.n_comps, ps.bitdepth, levels, reduce,
        ps.reversible, ps.used_mct, dict(zip(keys, out)), deltas)


def _region_impl(data: bytes, reduce: int, layers, region,
                 idx) -> CoefficientSet:
    with obs.stage(decoder_mod._metrics_sink, "decode.t2_parse") as st:
        if idx is not None:
            ps = sindex.skeleton(idx)
            if reduce < 0:
                raise InvalidParam(f"invalid reduce {reduce}")
            if layers is not None and layers < 1:
                raise InvalidParam(f"invalid layers {layers}")
            if reduce > ps.levels:
                raise InvalidParam(
                    f"reduce={reduce} exceeds {ps.levels} decomposition "
                    "levels")
        else:
            ps = parser.parse(data, reduce=reduce, layers=layers)
        st.items = ps.n_packets

    levels = ps.levels - reduce
    ry0, ry1, rx0, rx1 = decoder_mod._map_region(
        region, ps.width, ps.height, reduce)
    offs, _ = _grid_extents(ps, reduce, levels)
    keys = band_keys(levels)
    n_ty, n_tx = _tile_grid(ps)

    work = []               # (tidx, (ty, tx), plan, band windows)
    for tidx in range(n_ty * n_tx):
        y0, x0, th, tw = decoder_mod._tile_geometry(ps, tidx)
        ty0, tx0 = decoder_mod._reduced_dims(y0, x0, reduce)
        rh, rw = decoder_mod._reduced_dims(th, tw, reduce)
        wy0, wy1 = max(ry0 - ty0, 0), min(ry1 - ty0, rh)
        wx0, wx1 = max(rx0 - tx0, 0), min(rx1 - tx0, rw)
        if wy0 >= wy1 or wx0 >= wx1:
            continue
        bd = _band_dims(rh, rw, levels)
        wins = {}
        slots = []
        for res in range(1, levels + 1):
            for name in ("HL", "LH", "HH"):
                d = band_downsample(res, levels)
                _, _, bh, bw = bd[(res, name)]
                by0, by1 = band_window(wy0, wy1, d, bh)
                bx0, bx1 = band_window(wx0, wx1, d, bw)
                wins[(res, name)] = (by0, by1, bx0, bx1)
                slots.append((name, levels - res + 1, by0, by1, bx0,
                              bx1, float(ps.quants[(res, name)].delta)))
        d = band_downsample(0, levels)
        _, _, bh, bw = bd[(0, "LL")]
        by0, by1 = band_window(wy0, wy1, d, bh)
        bx0, bx1 = band_window(wx0, wx1, d, bw)
        wins[(0, "LL")] = (by0, by1, bx0, bx1)
        slots.append(("LL", levels, by0, by1, bx0, bx1,
                      float(ps.quants[(0, "LL")].delta)))
        work.append((tidx, divmod(tidx, n_tx),
                     _CoeffPlan(tuple(slots)), wins))

    if idx is not None:
        with obs.stage(decoder_mod._metrics_sink,
                       "decode.t2_parse") as st:
            n_before = ps.n_packets
            max_layers = ps.n_layers if layers is None else min(
                layers, ps.n_layers)
            sindex.parse_tiles(
                data, idx, ps,
                {tidx: decoder_mod._slot_windows(plan, levels)
                 for tidx, _, plan, _ in work},
                levels, max_layers)
            st.items = ps.n_packets - n_before

    # Output window rectangles on the global band planes, from the
    # participating tiles' windows (adjacent tiles' windows abut, so
    # min/max over tiles is exact).
    out_win = {}
    for key in keys:
        rect = None
        for _, (ty, tx), _, wins in work:
            by0, by1, bx0, bx1 = wins[key]
            roffs, coffs = offs[key]
            gy0, gy1 = roffs[ty] + by0, roffs[ty] + by1
            gx0, gx1 = coffs[tx] + bx0, coffs[tx] + bx1
            if rect is None:
                rect = [gy0, gy1, gx0, gx1]
            else:
                rect = [min(rect[0], gy0), max(rect[1], gy1),
                        min(rect[2], gx0), max(rect[3], gx1)]
        out_win[key] = tuple(rect) if rect else (0, 0, 0, 0)

    planes = {key: np.zeros((ps.n_comps,
                             out_win[key][1] - out_win[key][0],
                             out_win[key][3] - out_win[key][2]),
                            dtype=np.int32) for key in keys}
    tiles_by_idx = {t.idx: t for t in ps.tiles}
    n_blocks = n_dec = 0
    for tidx, (ty, tx), plan, wins in work:
        _poll()
        arrays, nb, nd = decoder_mod._tile_region_hvals(
            ps, tiles_by_idx[tidx], reduce, plan)
        n_blocks += nb
        n_dec += nd
        # Slot order is details (res 1..L) then LL; re-key and place.
        slot_keys = [(res, name) for res in range(1, levels + 1)
                     for name in ("HL", "LH", "HH")] + [(0, "LL")]
        for key, arr in zip(slot_keys, arrays):
            by0, by1, bx0, bx1 = wins[key]
            roffs, coffs = offs[key]
            oy = roffs[ty] + by0 - out_win[key][0]
            ox = coffs[tx] + bx0 - out_win[key][2]
            planes[key][:, oy:oy + (by1 - by0),
                        ox:ox + (bx1 - bx0)] = arr

    deltas = {key: float(ps.quants[key].delta) for key in keys}
    with obs.stage(decoder_mod._metrics_sink, "decode.coeff_dequant"):
        out = _run_dequant(ps.reversible,
                           tuple(deltas[k] for k in keys),
                           [planes[k] for k in keys])
    _count(ps, n_blocks, n_dec, region=True)
    return CoefficientSet(
        ps.width, ps.height, ps.n_comps, ps.bitdepth, levels, reduce,
        ps.reversible, ps.used_mct, dict(zip(keys, out)), deltas,
        region=tuple(int(v) for v in region), windows=out_win)


def _count(ps, n_blocks, n_dec, region: bool) -> None:
    sink = decoder_mod._metrics_sink
    if sink is None:
        return
    sink.count("decode.coeff_requests")
    sink.count("decode.blocks", n_blocks)
    sink.count("decode.mq_symbols", n_dec)
    if region:
        sink.count("decode.region_blocks", n_blocks)
    if ps.n_packets_skipped:
        sink.count("decode.packets_skipped", ps.n_packets_skipped)


def decode_to_coefficients(data: bytes, region: tuple | None = None,
                           reduce: int = 0, layers: int | None = None,
                           index=None) -> CoefficientSet:
    """Decode a JP2/JPX file or raw codestream to device-resident
    per-subband coefficient tensors (Tier-1 + dequantization only — no
    inverse DWT, color transform, or level shift).

    ``reduce``/``layers`` as in :func:`codec.decode.decode`;
    ``region=(x, y, w, h)`` returns only the mapped band windows, with
    Tier-1 running solely for the intersecting code-blocks (pass
    ``index`` — a PR 6 StreamIndex — to also seek Tier-2 straight to
    the intersecting packets). The result is bit-exact against slicing
    the same bands out of a full coefficient read (the
    :func:`band_window` rule). Malformed input raises the typed
    :class:`DecodeError`; impossible parameters raise
    :class:`InvalidParam`."""
    if not isinstance(data, (bytes, bytearray, memoryview)):
        raise TypeError("decode_to_coefficients() expects bytes")
    try:
        if region is not None:
            return _region_impl(bytes(data), int(reduce), layers,
                                region, index)
        return _full_impl(bytes(data), int(reduce), layers)
    except DecodeError:
        raise
    except (IndexError, KeyError, ValueError, OverflowError,
            struct.error) as exc:
        raise DecodeError(f"malformed codestream: {exc}") from exc
