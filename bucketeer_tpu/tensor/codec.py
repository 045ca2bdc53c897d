"""The general bit-plane tensor codec: arbitrary int/float tensors
through the image pipeline's own Tier-1 kernels.

``encode_tensor`` maps a tensor to 16-bit signed limb planes
(tensor/planes.py), carves them into the same 64x64 code-blocks the
image front-end uses, and routes them through the device CX/D
context-modeling scan chained into the device MQ arithmetic coder
(codec/cxd.py, :func:`~bucketeer_tpu.codec.cxd.run_device_mq`) — the
host never touches a symbol; it assembles finished byte segments into
the self-describing ``BTT1`` container (tensor/container.py). This is
the "RD-optimized trit-plane latent coding" shape from PAPERS.md
applied to our binary planes: checkpoint/activation tensors become
progressive bit-plane streams truncatable at any plane boundary.

Three backends share one output, byte for byte:

- ``device`` (default): CX/D scan -> MQ scan, both on device
  (cxd.run_device_mq);
- ``replay``: device CX/D scan, host MQ replay (cxd.run_cxd +
  t1_batch.encode_cxd) — the mode the byte-identity contract names;
- ``host``: the pure-host reference coder (t1.encode_block), no device
  at all — the oracle small tests compare the other two against
  (transitively byte-identical by the PR 3/PR 9 parity suites).

Decoding is host Tier-1 (codec/decode/t1_dec.py — the MQ state machine
is inherently serial), then the inverse plane mapping. Lossless for
every supported dtype, including IEEE NaN payloads and negative zeros
(an explicit escape list; see tensor/planes.py).

Rate control: every block's plane-boundary truncation points (the
``rate.truncation_lengths`` rule, bytes-at-boundary + 4 capped at the
stream) are recorded in the container, so :func:`truncate_tensor` cuts
an existing blob to ``planes=`` (keep the top-k absolute payload
planes) or ``rate=`` (byte budget, deepest global plane cut that fits)
by pure byte slicing — no recode, the trit-plane paper's progressive
property. ``encode_tensor(planes=k)`` instead floors the planes at
encode time, so the skipped planes cost no coding work at all.
"""
from __future__ import annotations

import contextlib
import os
import threading
import time
from functools import lru_cache

import numpy as np

from ..analysis import graftcost, retrace
from ..codec import t1, t1_batch
from ..codec import cxd as cxd_mod
from ..codec.decode import t1_dec
from ..codec.decode.errors import DecodeError
from ..codec.pipeline import _bucket, donate_argnums_if_supported
from . import container
from . import planes as _planes

BLOCK = 64
BLOCK_SAMPLES = container.BLOCK_SAMPLES

# Blocks per device chunk: bounds the HBM symbol buffer
# (N x max_syms(16) ~ 100 KB/block) while keeping the vmapped scan wide.
DEFAULT_CHUNK_BLOCKS = 64

# Every tensor block codes with the LL context tables: there is no
# subband orientation to exploit in a generic tensor, and one fixed
# class keeps device and host paths trivially in agreement.
BAND = "LL"

_metrics_sink = None


def set_metrics_sink(sink) -> None:
    """Install a metrics sink with ``record``/``count``; None disables
    (the server wires server.metrics.GLOBAL here, same seam as the
    encoder's)."""
    global _metrics_sink
    _metrics_sink = sink


_services = threading.local()


@contextlib.contextmanager
def tensor_services(check=None, launch=None):
    """Per-thread scheduler services — the tensor-codec mirror of the
    encoder's ``pipeline_services`` and the decoder's
    ``decode_services``. ``check`` is the deadline hook polled between
    chunks/blocks; ``launch`` (``callable(rows, floors, backend) ->
    (blocks, n_syms, device_seconds)``) routes device-backend chunks
    through the scheduler's pool so compatible chunks from concurrent
    tensor jobs merge into one launch. The scheduler installs both for
    ``kind="tensor"`` jobs."""
    prev = (getattr(_services, "check", None),
            getattr(_services, "launch", None))
    _services.check = check
    _services.launch = launch
    try:
        yield
    finally:
        _services.check, _services.launch = prev


def _poll() -> None:
    check = getattr(_services, "check", None)
    if check is not None:
        check()


# --- the device block packer ---------------------------------------------

def pack_program():
    """(traceable fn, device donate_argnums) for the tensor block
    packer — audit seam (analysis/deviceaudit.py). One flat int32 limb
    buffer becomes the (N, 64, 64) block batch the CX/D scan consumes
    (it stays in HBM) plus per-block magnitude maxima (the only small
    host fetch: nbps drive the pass tables). The donate spec is empty
    by verified fact: although the block output is a pure reshape of
    the input, no output matches the flat (N*4096,) aval, so XLA drops
    the alias — the audit's forced probe proves ``tf.aliasing_output``
    never appears."""
    import jax.numpy as jnp

    def body(flat):
        blocks = flat.reshape(-1, BLOCK, BLOCK)
        return blocks, jnp.abs(blocks).max(axis=(1, 2))

    return retrace.instrument("tensor_pack", body), ()


@lru_cache(maxsize=1)
def _compiled_pack():
    import jax

    fn, donate = pack_program()
    return jax.jit(fn, donate_argnums=donate_argnums_if_supported(*donate))


def fetch_block_meta(maxmag_dev) -> np.ndarray:
    """The pack stage's one device->host transfer: the (N,) per-block
    magnitude maxima (4 bytes/block — the blocks themselves stay in HBM
    for the CX/D scan). Sanctioned in rules_jax.D2H_SANCTIONED."""
    import jax

    return np.asarray(jax.device_get(maxmag_dev))


# --- encode ---------------------------------------------------------------

def _resolve_backend(device) -> str:
    if device is None:
        device = os.environ.get("BUCKETEER_TENSOR_BACKEND", "device")
    if device not in ("device", "replay", "host"):
        raise ValueError(
            f"unknown tensor backend {device!r}: expected device | "
            "replay | host")
    return device


def _chunk_blocks(chunk_blocks) -> int:
    if chunk_blocks is not None:
        return max(1, int(chunk_blocks))
    try:
        return max(1, int(os.environ.get(
            "BUCKETEER_TENSOR_CHUNK_BLOCKS", str(DEFAULT_CHUNK_BLOCKS))))
    except ValueError:
        return DEFAULT_CHUNK_BLOCKS


def _block_rows(limbs: np.ndarray) -> np.ndarray:
    """(K, n) limb planes -> (K * nb, 4096) int32 block rows,
    limb-major, tails zero-padded (zeros never become significant, so
    padding costs no symbols)."""
    k, n = limbs.shape
    nb = -(-n // BLOCK_SAMPLES) if n else 0
    rows = np.zeros((k, nb * BLOCK_SAMPLES), dtype=np.int32)
    rows[:, :n] = limbs
    return rows.reshape(k * nb, BLOCK_SAMPLES)


def _limb_bases(k: int, nb: int) -> np.ndarray:
    """Absolute payload-plane base of every block (limb-major order):
    limb j covers planes [(K-1-j)*16, (K-j)*16)."""
    return np.repeat(
        np.array([(k - 1 - j) * _planes.LIMB_BITS for j in range(k)],
                 dtype=np.int32), nb)


def _encode_host(rows: np.ndarray, floors: np.ndarray) -> list:
    out = []
    for row, floor in zip(rows, floors):
        _poll()
        block = row.reshape(BLOCK, BLOCK)
        mags = (np.abs(block).astype(np.uint32) >> floor) << floor
        out.append(t1.encode_block(mags, block < 0, BAND,
                                   floor=int(floor)))
    return out


def encode_chunk_device(rows: np.ndarray, floors: np.ndarray,
                        backend: str, device=None):
    """One chunk through the device: pack -> CX/D (-> MQ). Returns
    ([t1.CodedBlock], symbols, device_seconds). ``device`` (a
    ``jax.Device``) stages the limb buffer with a *committed*
    ``jax.device_put`` so the pack and every downstream device stage
    run on that core — the scheduler's pool workers use it; None keeps
    default placement."""
    import jax.numpy as jnp

    n = len(rows)
    nbuck = _bucket(n)
    flat = np.zeros(nbuck * BLOCK_SAMPLES, dtype=np.int32)
    flat[:n * BLOCK_SAMPLES] = rows.ravel()
    graftcost.record_bucket("tensor.blocks", n, nbuck)
    t0 = time.perf_counter()
    if device is not None:
        import jax
        staged = jax.device_put(flat, device)
    else:
        staged = jnp.asarray(flat)
    blocks_dev, maxmag_dev = _compiled_pack()(staged)
    maxmag = fetch_block_meta(maxmag_dev)[:n]
    nbps = np.zeros(n, dtype=np.int32)
    nz = maxmag > 0
    nbps[nz] = np.floor(np.log2(maxmag[nz].astype(np.float64))).astype(
        np.int32) + 1
    hs = np.full(n, BLOCK, dtype=np.int32)
    bandnames = [BAND] * n
    if backend == "device":
        res = cxd_mod.run_device_mq(blocks_dev, nbps, floors, bandnames,
                                    hs, hs, _planes.LIMB_BITS, 0)
        return res.blocks, res.total_syms, time.perf_counter() - t0
    streams = cxd_mod.run_cxd(blocks_dev, nbps, floors, bandnames, hs,
                              hs, _planes.LIMB_BITS, 0)
    dev_s = time.perf_counter() - t0
    return t1_batch.encode_cxd(streams), streams.total_syms, dev_s


def _to_tensor_block(blk: t1.CodedBlock) -> container.TensorBlock:
    cums = np.asarray([p.cum_length for p in blk.passes
                       if p.pass_type == 2], dtype=np.int64)
    return container.TensorBlock(blk.n_bitplanes, len(cums), blk.data,
                                 cums)


def encode_tensor(arr, planes: int | None = None,
                  rate: int | None = None, device: str | None = None,
                  chunk_blocks: int | None = None) -> bytes:
    """Encode a tensor to ``BTT1`` container bytes.

    ``planes=k`` keeps only the top ``k`` absolute payload planes
    (encode-time floors: the dropped planes cost no coding work);
    ``rate=b`` encodes losslessly and then truncates the blob to the
    deepest global plane cut fitting ``b`` bytes. ``device`` picks the
    backend (``device`` | ``replay`` | ``host``; env default
    ``BUCKETEER_TENSOR_BACKEND``) — all three are byte-identical.
    """
    arr = np.asarray(arr)
    spec = _planes.spec_for(arr.dtype)
    t_wall = time.perf_counter()
    backend = _resolve_backend(device)
    limbs = _planes.to_limbs(arr)
    negz = _planes.negative_zero_positions(arr, spec)
    rows = _block_rows(limbs)
    k = spec.n_limbs
    nb = len(rows) // k if k else 0
    total_bits = k * _planes.LIMB_BITS
    bases = _limb_bases(k, nb)
    if planes is not None:
        if planes < 0:
            raise ValueError(f"planes must be >= 0, got {planes}")
        cut = max(0, total_bits - int(planes))
    else:
        cut = 0
    floors = np.clip(cut - bases, 0, _planes.LIMB_BITS).astype(np.int32)

    coded: list = []
    n_syms = 0
    dev_s = 0.0
    chunk = _chunk_blocks(chunk_blocks)
    launch = getattr(_services, "launch", None)
    for off in range(0, len(rows), chunk):
        _poll()
        sub = rows[off:off + chunk]
        fsub = floors[off:off + chunk]
        if backend == "host":
            coded += _encode_host(sub, fsub)
        else:
            if backend == "device" and launch is not None:
                # Scheduler seam: the pool runs (and possibly merges)
                # the chunk on a free device; byte-identical because
                # per-block coding is independent of its batch-mates.
                blks, syms, ds = launch(sub, fsub, backend)
            else:
                blks, syms, ds = encode_chunk_device(sub, fsub, backend)
            coded += blks
            n_syms += syms
            dev_s += ds

    enc = container.EncodedTensor(
        spec, arr.shape, negz, [_to_tensor_block(b) for b in coded])
    blob = container.dump(enc)
    if _metrics_sink is not None:
        _metrics_sink.record("tensor.encode",
                             time.perf_counter() - t_wall,
                             items=arr.nbytes)
        if dev_s:
            _metrics_sink.record("tensor.encode_device", dev_s,
                                 items=n_syms)
        _metrics_sink.count("tensor.encode_blocks", len(coded))
        _metrics_sink.count("tensor.raw_bytes", arr.nbytes)
        _metrics_sink.count("tensor.coded_bytes", len(blob))
    if rate is not None:
        return truncate_tensor(blob, rate=rate)
    return blob


# --- truncation -----------------------------------------------------------

def _cut_kept(b: container.TensorBlock, base: int, cut: int) -> int:
    """Planes block ``b`` keeps under the absolute payload-plane
    ``cut`` (never more than it already has)."""
    floor_new = max(b.nbp - b.kept, min(cut - base, _planes.LIMB_BITS))
    return max(0, b.nbp - floor_new)


def _container_size(enc: container.EncodedTensor, cut: int,
                    bases: np.ndarray) -> int:
    """Serialized size of ``_apply_cut(enc, cut)`` from the parsed
    headers alone — no byte copies (rate= probes every cut, so this
    must be arithmetic, not a dump)."""
    size = 17 + 8 * len(enc.shape) + 8 * len(enc.neg_zeros)
    for b, base in zip(enc.blocks, bases):
        kept = _cut_kept(b, int(base), cut)
        size += 6 + 4 * kept
        if kept == b.kept:
            size += len(b.data)
        elif kept:
            size += int(b.cums[kept - 1])
    return size


def _apply_cut(enc: container.EncodedTensor,
               cut: int) -> container.EncodedTensor:
    """Truncate every block at the absolute payload-plane ``cut``
    (drop planes below it) by slicing at the recorded plane-boundary
    lengths — no recode."""
    k = enc.spec.n_limbs
    nb = enc.blocks_per_limb
    bases = _limb_bases(k, nb)
    blocks = []
    for b, base in zip(enc.blocks, bases):
        kept = _cut_kept(b, int(base), cut)
        if kept == b.kept:
            blocks.append(b)
        elif kept == 0:
            blocks.append(container.TensorBlock(
                b.nbp, 0, b"", np.zeros(0, dtype=np.int64)))
        else:
            end = int(b.cums[kept - 1])
            blocks.append(container.TensorBlock(
                b.nbp, kept, b.data[:end], b.cums[:kept]))
    return container.EncodedTensor(enc.spec, enc.shape, enc.neg_zeros,
                                   blocks)


def truncate_tensor(blob: bytes, planes: int | None = None,
                    rate: int | None = None) -> bytes:
    """Progressively truncate an encoded tensor at plane boundaries.

    ``planes=k``: keep the top ``k`` absolute payload planes.
    ``rate=b``: the deepest (least destructive) global plane cut whose
    container fits ``b`` bytes; the header itself is the floor — a
    budget below it returns the fully-cut container.
    """
    enc = container.parse(blob)
    total_bits = enc.spec.n_limbs * _planes.LIMB_BITS
    if (planes is None) == (rate is None):
        raise ValueError("pass exactly one of planes= / rate=")
    if planes is not None:
        if planes < 0:
            raise ValueError(f"planes must be >= 0, got {planes}")
        return container.dump(_apply_cut(enc, total_bits - min(
            int(planes), total_bits)))
    if rate < 0:
        raise ValueError(f"rate must be >= 0, got {rate}")
    # Candidate sizes are pure header arithmetic (_container_size);
    # only the winning cut is serialized.
    bases = _limb_bases(enc.spec.n_limbs, enc.blocks_per_limb)
    for cut in range(0, total_bits + 1):
        if _container_size(enc, cut, bases) <= rate:
            break
    else:
        cut = total_bits
    return container.dump(_apply_cut(enc, cut))


# --- decode ---------------------------------------------------------------

def decode_tensor(blob: bytes, planes: int | None = None) -> np.ndarray:
    """Decode ``BTT1`` container bytes back to a tensor. A losslessly
    coded blob round-trips bit-exact (NaN payloads and negative zeros
    included); a truncated blob (or ``planes=k``, an on-the-fly cut)
    reconstructs missing planes at the EBCOT midpoint, floored — the
    same deterministic rule the image decoder's quality layers use.
    Malformed input raises the typed :class:`DecodeError`."""
    if planes is not None and planes < 0:
        raise ValueError(f"planes must be >= 0, got {planes}")
    t_wall = time.perf_counter()
    try:
        enc = container.parse(blob)
        total_bits = enc.spec.n_limbs * _planes.LIMB_BITS
        if planes is not None:
            enc = _apply_cut(enc, total_bits - min(int(planes),
                                                   total_bits))
        k = enc.spec.n_limbs
        nb = enc.blocks_per_limb
        n = enc.n_elements
        limbs = np.zeros((k, nb * BLOCK_SAMPLES), dtype=np.int32)
        n_dec = 0
        for i, b in enumerate(enc.blocks):
            _poll()
            if not (b.kept and b.nbp):
                continue
            hv, nd = t1_dec.decode_block(
                b.data, b.nbp, 3 * b.kept - 2, BAND, BLOCK, BLOCK)
            n_dec += nd
            mag = np.abs(hv) >> 1
            j, bi = divmod(i, nb)
            limbs[j, bi * BLOCK_SAMPLES:(bi + 1) * BLOCK_SAMPLES] = \
                np.where(hv < 0, -mag, mag).ravel()
        out = _planes.from_limbs(limbs[:, :n], enc.spec, enc.shape,
                                 enc.neg_zeros)
    except DecodeError:
        raise
    except (IndexError, KeyError, ValueError, OverflowError) as exc:
        raise DecodeError(f"malformed tensor container: {exc}") from exc
    if _metrics_sink is not None:
        _metrics_sink.record("tensor.decode",
                             time.perf_counter() - t_wall,
                             items=n_dec)
        _metrics_sink.count("tensor.decode_blocks", len(enc.blocks))
    return out


def tensor_stats(blob: bytes) -> dict:
    """Cheap container metadata for the HTTP layer (no Tier-1 work)."""
    enc = container.parse(blob)
    raw = enc.n_elements * enc.spec.itemsize
    coded = len(blob)
    return {
        "dtype": enc.spec.name,
        "shape": list(enc.shape),
        "limbs": enc.spec.n_limbs,
        "blocks": len(enc.blocks),
        "planes": enc.pcap,
        "raw_bytes": raw,
        "coded_bytes": coded,
        "ratio": round(raw / coded, 4) if coded else 0.0,
    }
