"""Native Tier-1 decoder (``t1_decode_blocks``) vs the pure-Python
reference ``t1_dec.decode_block``: the same signed half-magnitudes bit
for bit and the same MQ decision counts, on every band, block shape and
pass truncation, on damaged streams, and for each malformed header the
Python decoder rejects. Then the read path: the native decoder runs the
tile reads, releases the GIL, and the fallback decodes the same pixels.
"""
import dataclasses
import threading

import numpy as np
import pytest

from bucketeer_tpu import native
from bucketeer_tpu.codec import encoder, t1, t1_batch
from bucketeer_tpu.codec.decode import decoder as dec_mod
from bucketeer_tpu.codec.decode import t1_dec
from bucketeer_tpu.codec.decode.errors import DecodeError
from bucketeer_tpu.codec.encoder import EncodeParams
from bucketeer_tpu.converters.reader import TpuReader
from bucketeer_tpu.server.metrics import Metrics

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native T1 unavailable (no g++?)")

BANDS = ["LL", "HL", "LH", "HH"]
SHAPES = [(1, 1), (3, 17), (17, 3), (64, 64), (32, 64)]


def _coded(seed: int, shape: tuple, band: str, bits: int,
           density: float = 0.6) -> t1.CodedBlock:
    rng = np.random.default_rng(seed)
    mags = ((rng.random(shape) < density)
            * rng.integers(0, 1 << bits, size=shape)).astype(np.uint32)
    mags.flat[0] = (1 << bits) - 1          # every plane is coded
    signs = rng.random(shape) < 0.5
    return t1.encode_block(mags, signs, band)


def _native(specs: list) -> tuple:
    hvs, n_dec, on_native = t1_dec.decode_blocks(specs)
    assert on_native
    return hvs, n_dec


def _assert_matches_python(specs: list) -> None:
    """One native call over every spec against decode_block on each."""
    hvs, n_dec = _native(specs)
    assert len(hvs) == len(specs)
    total = 0
    for spec, got in zip(specs, hvs):
        want, n = t1_dec.decode_block(*spec)
        total += n
        assert got.dtype == np.int32 and got.shape == want.shape
        np.testing.assert_array_equal(got, want)
    assert n_dec == total


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: f"{s[0]}x{s[1]}")
@pytest.mark.parametrize("band", BANDS)
def test_every_pass_truncation_matches_python(band, shape):
    """Every truncation the Tier-2 parser can hand over: the first k
    passes, the segment cut at the encoder's truncation length for k."""
    bits = 5 if shape[0] * shape[1] >= 2048 else 8
    blk = _coded(10 * BANDS.index(band) + SHAPES.index(shape), shape, band,
                 bits)
    assert blk.n_bitplanes == bits
    assert len(blk.passes) == t1_dec.max_passes(bits)
    specs = [(blk.data[:blk.passes[k - 1].cum_length], blk.n_bitplanes, k,
              band, *shape) for k in range(1, len(blk.passes) + 1)]
    _assert_matches_python(specs)


@pytest.mark.parametrize("band", BANDS)
def test_deep_lossless_blocks_match_python(band):
    """Lossless 16-bit content: 17 coded planes, decoded in full, give
    back every magnitude exactly (|hval| = 2*mag + 1)."""
    rng = np.random.default_rng(17)
    mags = rng.integers(0, 1 << 17, size=(24, 40)).astype(np.uint32)
    mags[0, 0] = (1 << 17) - 1
    mags[5, 7] = 0
    signs = rng.random(mags.shape) < 0.5
    blk = t1.encode_block(mags, signs, band)
    assert blk.n_bitplanes == 17
    spec = (blk.data, 17, len(blk.passes), band, *mags.shape)
    _assert_matches_python([spec])
    (hv,), _ = _native([spec])
    want = np.where(mags > 0, 2 * mags.astype(np.int64) + 1, 0)
    np.testing.assert_array_equal(np.abs(hv), want)
    np.testing.assert_array_equal(hv < 0, signs & (mags > 0))


def test_largest_magnitude_decodes_without_overflow():
    """30 planes of all-ones: the largest half-magnitude a block can
    carry, 2^31 - 1, sits just under the int32 overflow check."""
    mags = np.full((4, 4), (1 << 30) - 1, dtype=np.uint32)
    signs = np.eye(4, dtype=bool)
    blk = t1.encode_block(mags, signs, "LL")
    spec = (blk.data, 30, len(blk.passes), "LL", 4, 4)
    _assert_matches_python([spec])
    (hv,), _ = _native([spec])
    assert int(np.abs(hv.astype(np.int64)).max()) == (1 << 31) - 1


def _damaged(kind: str, seed: int) -> tuple:
    rng = np.random.default_rng(seed)
    band = BANDS[seed % 4]
    shape = SHAPES[seed % len(SHAPES)]
    blk = _coded(seed, shape, band, 7)
    npasses = int(rng.integers(1, len(blk.passes) + 1))
    if kind == "truncated":
        data = blk.data[:int(rng.integers(0, len(blk.data)))]
    elif kind == "random":
        data = rng.integers(0, 256, size=int(rng.integers(1, 400)),
                            dtype=np.uint8).tobytes()
    elif kind == "all_ff":
        data = b"\xff" * int(rng.integers(1, 40))
    elif kind == "markers":                  # 0xFF before either side
        data = rng.choice([0xFF, 0x8F, 0x90, 0x00, 0x7F], size=int(
            rng.integers(1, 400))).astype(np.uint8).tobytes()
    else:                                    # no bytes at all
        data = b""
    return data, blk.n_bitplanes, npasses, band, *shape


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("kind", ["truncated", "random", "all_ff", "markers",
                                  "empty"])
def test_damaged_streams_match_python(kind, seed):
    """Bytes past the end read as 0xFF on both sides, so a cut or
    garbage segment decodes to the same (meaningless) samples — no
    crash, no read past the segment. ``markers`` puts 0xFF before
    bytes on both sides of the 0x8F marker threshold of BYTEIN."""
    specs = [_damaged(kind, seed * 8 + j) for j in range(8)]
    _assert_matches_python(specs)


@pytest.mark.parametrize("nbps, npasses, message", [
    (31, 1, "31 bit-planes exceeds the 30-plane cap"),
    (4, 11, "11 passes exceeds the 10 possible for 4 bit-planes"),
    (1, 2, "2 passes exceeds the 1 possible for 1 bit-planes"),
])
def test_malformed_pass_counts_raise_like_python(nbps, npasses, message):
    """A header no conforming encoder emits: the same typed DecodeError
    as decode_block, for the first bad block of the batch."""
    blk = _coded(3, (8, 8), "HH", 4)
    good = (blk.data, blk.n_bitplanes, 4, "HH", 8, 8)
    bad = (blk.data, nbps, npasses, "HH", 8, 8)
    with pytest.raises(DecodeError) as want:
        t1_dec.decode_block(*bad)
    assert str(want.value) == message
    with pytest.raises(DecodeError) as got:
        t1_dec.decode_blocks([good, bad, (b"", 40, 200, "LL", 2, 2)])
    assert str(got.value) == message


def test_empty_blocks_and_batches():
    """Blocks with no planes or no passes are zeros with no decisions;
    an empty batch makes no call; a negative extent never reaches it."""
    blk = _coded(5, (3, 17), "LH", 3)
    specs = [(blk.data, 0, 4, "LH", 3, 17), (blk.data, 3, 0, "LH", 3, 17),
             (b"", 40, 0, "LL", 5, 5), (blk.data, 3, 7, "LH", 3, 17)]
    _assert_matches_python(specs)
    hvs, _ = _native(specs)
    assert not hvs[0].any() and not hvs[1].any() and not hvs[2].any()
    assert t1_dec.decode_blocks([]) == ([], 0, True)
    with pytest.raises(DecodeError):
        t1_dec.decode_blocks(specs + [(blk.data, 3, 7, "LH", -3, 17)])


def test_native_decode_releases_gil_and_records_pool():
    """Four viewers decode at once only if the native call releases the
    GIL: pure-Python work must make progress while it runs. The call
    records the pool it fanned out to, capped by the block count."""
    rng = np.random.default_rng(11)
    mags = [(rng.random((64, 64)) * 4096).astype(np.uint32)
            for _ in range(96)]
    coded = t1_batch.encode_blocks(
        [(m, rng.random((64, 64)) < 0.5, "LL", None) for m in mags])
    specs = [(b.data, b.n_bitplanes, len(b.passes), "LL", 64, 64)
             for b in coded]
    stop = threading.Event()
    progress = [0]

    def spin():
        while not stop.is_set():
            progress[0] += 1

    spinner = threading.Thread(target=spin)
    spinner.start()
    try:
        before = progress[0]
        _native(specs)
        during = progress[0] - before
    finally:
        stop.set()
        spinner.join()
    assert during > 50_000, (
        f"only {during} Python iterations ran concurrently with the "
        "native Tier-1 decode — the GIL appears held for the call")
    call = t1_batch.last_native_call
    assert call["fn"] == "t1_decode_blocks"
    assert call["n_blocks"] == len(specs)
    assert call["threads"] == min(t1_batch.default_threads(), len(specs))
    _native(specs[:2])
    assert t1_batch.last_native_call["threads"] == \
        min(t1_batch.default_threads(), 2)


def _lossy_region_jp2(tmp_path, size=128):
    rng = np.random.default_rng(24)
    y, x = np.mgrid[0:size, 0:size]
    base = (96 + 80 * np.sin(x / 9.0) * np.cos(y / 13.0))[..., None]
    img = np.clip(base + rng.normal(0, 12, (size, size, 3)), 0,
                  255).astype(np.uint8)
    params = dataclasses.replace(EncodeParams.kakadu_recipe(lossless=False),
                                 tile_size=64, levels=3)
    path = tmp_path / "lossy.jp2"
    path.write_bytes(encoder.encode_jp2(img, 8, params))
    return str(path)


def _reads(path: str) -> tuple:
    """A tile-server session through TpuReader: region reads at reduce
    0 and 1 and a reduce-2 overview, with the decoder's counters."""
    sink = Metrics()
    dec_mod.set_metrics_sink(sink)
    try:
        reader = TpuReader(cache_mb=0, metrics=sink)
        out = [reader.read(path, reduce=r, region=region)
               for r, region in [(0, (32, 16, 64, 64)), (1, (0, 0, 128, 96)),
                                 (2, (0, 0, 128, 128))]]
    finally:
        dec_mod.set_metrics_sink(None)
    return out, sink.report()["counters"]


def test_region_reads_decode_natively(tmp_path):
    _, counters = _reads(_lossy_region_jp2(tmp_path))
    assert counters["decode.native_blocks"] > 0
    assert counters["decode.native_blocks"] == counters["decode.blocks"]
    assert counters["decode.python_blocks"] == 0


def test_python_fallback_decodes_the_same_pixels(tmp_path, monkeypatch):
    """With BUCKETEER_NO_NATIVE=1 the Python loop decodes every block,
    counted in decode.python_blocks, to the same pixels bit for bit."""
    path = _lossy_region_jp2(tmp_path)
    fast, _ = _reads(path)
    monkeypatch.setenv("BUCKETEER_NO_NATIVE", "1")
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)
    assert native.load() is None
    slow, counters = _reads(path)
    assert counters["decode.python_blocks"] == counters["decode.blocks"] > 0
    assert counters["decode.native_blocks"] == 0
    for a, b in zip(fast, slow):
        np.testing.assert_array_equal(a, b)
