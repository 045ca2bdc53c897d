"""Batched Tier-1 dispatch: native C++ thread pool when available, pure
Python fallback otherwise (reference analog: ConverterFactory probing for
Kakadu and falling back, converters/ConverterFactory.java:37-47).

The whole image's code-blocks go through one call so the native thread
pool sees the full parallelism (blocks are independent — SURVEY.md §7).
"""
from __future__ import annotations

import logging
import os
import threading

import numpy as np

from .. import native, obs
from ..analysis.contracts import contract
from . import t1

LOG = logging.getLogger(__name__)
_BAND_CLS = t1.BAND_CLS        # single source of the band-class table

# Concurrency bookkeeping for the native dispatch. ctypes releases the
# GIL for the duration of every CDLL call (only PyDLL keeps it), so the
# encoder's host-coding worker genuinely overlaps the main thread's
# device dispatch — tests/test_native_t1.py proves it by running Python
# work concurrently with a native call. Each native entry records the
# thread-pool size it fanned out to so sizing regressions (e.g. an env
# override silently pinning the pool to 1) are observable.
last_native_call: dict = {}


def _note_call(fn: str, n_blocks: int, threads: int) -> None:
    last_native_call.update(fn=fn, n_blocks=n_blocks, threads=threads)


# Blocks coded by the pure-Python coder because native.load() returned
# None. A chunk that drops to Python codes ~500x slower with no error, so
# callers that require the native coder (chip_smoke.py) check this stays
# 0; native.available() alone cannot see a fallback on some chunks only.
python_coded_blocks = 0
_python_lock = threading.Lock()


def _note_python(n_blocks: int) -> None:
    global python_coded_blocks
    with _python_lock:
        first = python_coded_blocks == 0
        python_coded_blocks += n_blocks
    if first and n_blocks and not os.environ.get("BUCKETEER_NO_NATIVE"):
        LOG.warning("native Tier-1 library unavailable: coding with the "
                    "pure-Python coder")


def default_threads() -> int:
    env = os.environ.get("BUCKETEER_T1_THREADS")
    if env:
        return max(1, int(env))
    return max(1, (os.cpu_count() or 2) - 1)


def _correct(pdist: np.ndarray, ptype: np.ndarray, pplane: np.ndarray,
             block: np.ndarray, exact) -> None:
    """Replace the host coder's fractionless per-pass distortion
    estimates with the device front-end's exact per-plane sums, in
    place on the flat pass arrays (``block`` is each pass's block).

    The packed payload ships no fractional-magnitude bits (and, under a
    bit-plane floor, no low integer bits), so native Tier-1's midpoint
    estimates are biased; the device computed the exact per-plane
    significance/refinement distortion totals from the full fixed-point
    coefficients (frontend._frontend_body), ``exact = (sigd, refd)``,
    each (n_blocks, P). Pass-level granularity is recovered by scaling
    each pass in plane p by the exact/estimated plane-total ratio for
    its kind (sig = SPP+CP, ref = MRP). A zero estimate cannot be
    apportioned and is kept (the hull treats the pass as free
    distortion-wise either way)."""
    sigd, refd = exact
    ref = ptype == 1
    key = (block * sigd.shape[1] + pplane) * 2 + ref
    # bincount adds each key's weights in pass order: the estimate is
    # the same float a pass-by-pass running sum gives.
    est = np.bincount(key, weights=pdist)[key]
    ex = np.where(ref, refd[block, pplane], sigd[block, pplane])
    ok = (est > 0.0) & (ex >= 0.0)
    # The ratio and the product in the exact sums' precision (float32
    # from the device), as scalar numpy arithmetic on them would round.
    ratio = ex[ok] / est[ok].astype(ex.dtype)
    pdist[ok] = pdist[ok].astype(ratio.dtype) * ratio


def correct_distortions(blocks: list, exact) -> None:
    """:func:`_correct` on blocks already built: the pure-Python
    fallback of :func:`encode_packed`."""
    passes = [p for b in blocks for p in b.passes]
    if not passes:
        return
    k = len(passes)
    pdist = np.fromiter((p.dist_reduction for p in passes), np.float64, k)
    ptype = np.fromiter((p.pass_type for p in passes), np.int64, k)
    pplane = np.fromiter((p.bitplane for p in passes), np.int64, k)
    block = np.repeat(np.arange(len(blocks)),
                      [len(b.passes) for b in blocks])
    _correct(pdist, ptype, pplane, block, exact)
    for p, d in zip(passes, pdist.tolist()):
        p.dist_reduction = d


def _collect(lib, handle, n: int, exact=None) -> list:
    """Pull a native T1Result handle into [t1.CodedBlock]: one native
    copy of every block's bytes and passes into flat arrays, the
    distortion correction on those arrays when ``exact`` (the device's
    per-plane ``(sigd, refd)``) is given, then the objects in bulk."""
    try:
        nbps = np.zeros(n, dtype=np.int32)
        npasses = np.zeros(n, dtype=np.int32)
        nbytes = np.zeros(n, dtype=np.int64)
        lib.t1_block_sizes(handle, nbps.ctypes.data, npasses.ctypes.data,
                           nbytes.ctypes.data)
        boffs = np.concatenate(([0], np.cumsum(nbytes))).tolist()
        poffs = np.concatenate(([0], np.cumsum(npasses))).tolist()
        k = poffs[-1]
        data = np.empty(max(boffs[-1], 1), dtype=np.uint8)
        ptype = np.empty(max(k, 1), dtype=np.int32)
        pplane = np.empty(max(k, 1), dtype=np.int32)
        plen = np.empty(max(k, 1), dtype=np.int64)
        pdist = np.empty(max(k, 1), dtype=np.float64)
        lib.t1_result_copy(handle, data.ctypes.data, ptype.ctypes.data,
                           pplane.ctypes.data, plen.ctypes.data,
                           pdist.ctypes.data)
    finally:
        lib.t1_result_free(handle)
    ptype, pplane, plen, pdist = ptype[:k], pplane[:k], plen[:k], pdist[:k]
    if exact is not None:
        _correct(pdist, ptype, pplane, np.repeat(np.arange(n), npasses),
                 exact)
    buf = data[:boffs[-1]].tobytes()
    passes = list(map(t1.PassInfo, ptype.tolist(), pplane.tolist(),
                      plen.tolist(), pdist.tolist()))
    return [t1.CodedBlock(buf[boffs[i]:boffs[i + 1]], nbp,
                          passes[poffs[i]:poffs[i + 1]])
            for i, nbp in enumerate(nbps.tolist())]


@contract(shapes={"payload": ("R", 512), "offsets": ("n1",),
                  "nbps": ("n",), "floors": ("n",), "hs": ("n",),
                  "ws": ("n",)},
          dtypes={"payload": "uint8", "offsets": "integer",
                  "nbps": "integer", "floors": "integer",
                  "hs": "integer", "ws": "integer"})
def encode_packed(payload: np.ndarray, offsets: np.ndarray,
                  nbps: np.ndarray, floors: np.ndarray,
                  hs: np.ndarray, ws: np.ndarray,
                  bands: list, sink=None, exact=None) -> list:
    """Tier-1 over the device front-end's packed bitmap payload
    (codec/frontend.py): payload (R, 512) uint8 rows, offsets (n+1,)
    row offsets per block, per-block nbps/floors/dims and band names.
    ``exact``: the device's per-plane ``(sigd, refd)`` distortion sums
    (lossy), which replace the coder's estimates (:func:`_correct`).
    Returns [t1.CodedBlock] in block order. The native call alone is
    the ``t1.native`` stage of ``sink`` (items = blocks)."""
    n = len(nbps)
    lib = native.load()
    if lib is not None and n:
        cls = np.fromiter(map(_BAND_CLS.__getitem__, bands), np.int32, n)
        # Bind every converted array to a local: .ctypes.data of an
        # unnamed temporary is a dangling pointer by call time.
        payload = np.ascontiguousarray(payload, dtype=np.uint8)
        offs = np.ascontiguousarray(offsets[:n], dtype=np.int64)
        nbps_c = np.ascontiguousarray(nbps, dtype=np.int32)
        floors_c = np.ascontiguousarray(floors, dtype=np.int32)
        hs_c = np.ascontiguousarray(hs, dtype=np.int32)
        ws_c = np.ascontiguousarray(ws, dtype=np.int32)
        threads = default_threads()
        _note_call("t1_encode_packed", n, threads)
        with obs.stage(sink, "t1.native", items=n):
            handle = lib.t1_encode_packed(
                n, payload.ctypes.data, offs.ctypes.data,
                nbps_c.ctypes.data, floors_c.ctypes.data, hs_c.ctypes.data,
                ws_c.ctypes.data, cls.ctypes.data, threads)
        return _collect(lib, handle, n, exact)
    _note_python(n)
    out = []
    for i in range(n):
        if nbps[i] <= floors[i]:
            out.append(t1.CodedBlock(b"", 0))
            continue
        from . import frontend
        mags, negs = frontend.unpack_block(payload, int(offsets[i]),
                                           int(nbps[i]), int(floors[i]),
                                           int(hs[i]), int(ws[i]))
        out.append(t1.encode_block(mags, negs, bands[i],
                                   floor=int(floors[i])))
    if exact is not None:
        correct_distortions(out, exact)
    return out


def encode_blocks(specs: list) -> list:
    """specs: [(mags uint32 (h,w), signs bool (h,w), band_name,
    fracs uint8 (h,w) | None)] -> [t1.CodedBlock] in order."""
    lib = native.load()
    if lib is None or not specs:
        _note_python(len(specs))
        return [t1.encode_block(m, s, b, f) for m, s, b, f in specs]

    n = len(specs)
    offsets = np.zeros(n + 1, dtype=np.int64)
    hs = np.zeros(n, dtype=np.int32)
    ws = np.zeros(n, dtype=np.int32)
    cls = np.zeros(n, dtype=np.int32)
    any_fracs = any(f is not None for _, _, _, f in specs)
    for i, (m, _, band, _) in enumerate(specs):
        hs[i], ws[i] = m.shape
        cls[i] = _BAND_CLS[band]
        offsets[i + 1] = offsets[i] + m.size
    total = int(offsets[-1])
    mags = np.empty(total, dtype=np.uint32)
    negs = np.empty(total, dtype=np.uint8)
    fracs = np.zeros(total, dtype=np.uint8) if any_fracs else None
    for i, (m, s, _, f) in enumerate(specs):
        mags[offsets[i]:offsets[i + 1]] = np.ascontiguousarray(
            m, dtype=np.uint32).ravel()
        negs[offsets[i]:offsets[i + 1]] = np.ascontiguousarray(
            s, dtype=np.uint8).ravel()
        if f is not None:
            fracs[offsets[i]:offsets[i + 1]] = np.ascontiguousarray(
                f, dtype=np.uint8).ravel()

    threads = default_threads()
    _note_call("t1_encode_blocks", n, threads)
    handle = lib.t1_encode_blocks(
        n, mags.ctypes.data, negs.ctypes.data,
        fracs.ctypes.data if fracs is not None else None,
        offsets.ctypes.data,
        hs.ctypes.data, ws.ctypes.data, cls.ctypes.data, threads)
    return _collect(lib, handle, n)


def encode_cxd(streams) -> list:
    """MQ replay of precomputed device CX/D streams (codec/cxd.py) —
    the host half of the tensor codec's device-CX/D backend. Native
    thread pool when available, pure-Python MQEncoder replay otherwise.
    Returns [t1.CodedBlock] in block order, byte-identical to what
    encode_packed would have produced from the same coefficients."""
    from . import cxd

    n = len(streams.nbps)
    lib = native.load()
    if lib is not None and n:
        payload = np.ascontiguousarray(streams.payload, dtype=np.uint8)
        row_offs = np.ascontiguousarray(streams.row_offsets,
                                        dtype=np.int64)
        nbps_c = np.ascontiguousarray(streams.nbps, dtype=np.int32)
        p_offs = np.ascontiguousarray(streams.pass_offsets,
                                      dtype=np.int64)
        p_types = np.ascontiguousarray(streams.pass_types, dtype=np.int32)
        p_planes = np.ascontiguousarray(streams.pass_planes,
                                        dtype=np.int32)
        p_nsyms = np.ascontiguousarray(streams.pass_nsyms, dtype=np.int32)
        p_dists = np.ascontiguousarray(streams.pass_dists,
                                       dtype=np.float64)
        threads = default_threads()
        _note_call("t1_encode_cxd", n, threads)
        handle = lib.t1_encode_cxd(
            n, payload.ctypes.data, row_offs.ctypes.data,
            nbps_c.ctypes.data, p_offs.ctypes.data, p_types.ctypes.data,
            p_planes.ctypes.data, p_nsyms.ctypes.data,
            p_dists.ctypes.data, threads)
        return _collect(lib, handle, n)

    _note_python(n)
    out = []
    for b in range(n):
        p0, p1 = int(streams.pass_offsets[b]), int(
            streams.pass_offsets[b + 1])
        n_syms = int(streams.pass_nsyms[p0:p1].sum())
        start = int(streams.row_offsets[b])
        n_rows = -(-n_syms // cxd.SYMS_PER_ROW)
        syms = cxd.unpack6(streams.payload[start:start + n_rows], n_syms)
        out.append(cxd.replay_block(
            syms, int(streams.nbps[b]), p1 - p0, streams.pass_types[p0:p1],
            streams.pass_planes[p0:p1], streams.pass_nsyms[p0:p1],
            streams.pass_dists[p0:p1]))
    return out
