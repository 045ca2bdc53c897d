"""Native C++ Tier-1 coder vs the pure-Python reference: bit-exact data,
identical pass metadata (truncation lengths, distortion estimates).
The analog of the reference's converter-parity concern (Kakadu vs
OpenJPEG output), but enforced to the byte.
"""
import os
import time

import numpy as np
import pytest

from bucketeer_tpu import native
from bucketeer_tpu.codec import encoder, rate as rate_mod, t1, t1_batch
from bucketeer_tpu.codec.encoder import EncodeParams
from bucketeer_tpu.server.metrics import Metrics

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native T1 unavailable (no g++?)")


def _random_blocks(rng, n=12):
    specs = []
    for i in range(n):
        h = int(rng.integers(1, 65))
        w = int(rng.integers(1, 65))
        # Mix of sparse (mostly-zero) and dense blocks across magnitudes.
        density = rng.choice([0.02, 0.3, 0.9])
        mags = (rng.random((h, w)) < density) * rng.integers(
            0, 1 << int(rng.integers(1, 14)), size=(h, w))
        signs = rng.random((h, w)) < 0.5
        band = ["LL", "HL", "LH", "HH"][i % 4]
        # Half the blocks carry fractional magnitude bits (lossy path).
        fracs = (rng.integers(0, 128, size=(h, w)).astype(np.uint8)
                 if i % 2 else None)
        specs.append((mags.astype(np.uint32), signs, band, fracs))
    specs.append((np.zeros((64, 64), np.uint32),
                  np.zeros((64, 64), bool), "HL", None))  # all-zero block
    return specs


def test_native_matches_python_bit_exact(rng):
    specs = _random_blocks(rng)
    got = t1_batch.encode_blocks(specs)
    for (m, s, band, f), blk in zip(specs, got):
        ref = t1.encode_block(m, s, band, f)
        assert blk.data == ref.data
        assert blk.n_bitplanes == ref.n_bitplanes
        assert len(blk.passes) == len(ref.passes)
        for gp, rp in zip(blk.passes, ref.passes):
            assert gp.pass_type == rp.pass_type
            assert gp.bitplane == rp.bitplane
            assert gp.cum_length == rp.cum_length
            assert gp.dist_reduction == pytest.approx(rp.dist_reduction,
                                                      rel=1e-12, abs=1e-9)


def test_python_fallback_when_disabled(rng, monkeypatch):
    specs = _random_blocks(rng, n=2)
    ref = [t1.encode_block(m, s, b, f) for m, s, b, f in specs]
    monkeypatch.setattr(native, "load", lambda: None)
    before = t1_batch.python_coded_blocks
    got = t1_batch.encode_blocks(specs)
    for g, r in zip(got, ref):
        assert g.data == r.data
    # The fallback is counted, so a caller that needs the native coder
    # (chip_smoke.py) sees it even when native.available() is True later.
    assert t1_batch.python_coded_blocks == before + len(specs)


def test_concurrent_first_load_waits_for_the_build(tmp_path, monkeypatch):
    """The host Tier-1 pool's threads call load() at once on a fresh
    checkout: every caller gets the library the first one builds —
    none takes the pure-Python coder while the build runs."""
    import threading

    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)
    monkeypatch.setattr(native, "_LIB_PATH", tmp_path / "libt1.so")
    build = native._build

    def slow_build(out=None):
        time.sleep(0.3)
        return build(out)

    monkeypatch.setattr(native, "_build", slow_build)
    got = []
    threads = [threading.Thread(target=lambda: got.append(native.load()))
               for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    assert len(got) == 8 and got[0] is not None
    assert all(lib is got[0] for lib in got)


def _photo(rng, shape, bitdepth):
    """A smooth image with sensor noise at ``bitdepth`` bits."""
    h, w = shape[:2]
    y, x = np.mgrid[0:h, 0:w]
    scale = 1 << (bitdepth - 8)
    base = (120 + 80 * np.sin(x / 17.0) * np.cos(y / 13.0)) * scale
    if len(shape) == 3:
        base = base[..., None]
    img = base + rng.normal(0, 8 * scale, shape)
    dtype = np.uint8 if bitdepth == 8 else np.uint16
    return np.clip(img, 0, (1 << bitdepth) - 1).astype(dtype)


def _starve_first_floors(monkeypatch):
    """Floors at every block's top plane on an encode's first estimate:
    they code nothing, so the encode reruns its floors."""
    real = rate_mod.estimate_floors
    calls = []

    def starve_first(nbps, *args, **kwargs):
        floors, lam = real(nbps, *args, **kwargs)
        calls.append(None)
        if len(calls) == 1:
            floors = np.asarray(nbps, dtype=floors.dtype).copy()
        return floors, lam

    monkeypatch.setattr(rate_mod, "estimate_floors", starve_first)


# (image shape, bit depth, EncodeParams fields, force a floor rerun)
_WHOLE_ENCODES = {
    "gray8_lossless": ((64, 64), 8, {"lossless": True, "levels": 2},
                       False),
    "rgb8_rate_floor_rerun": ((64, 64, 3), 8,
                              {"lossless": False, "levels": 2,
                               "rate": 1.5, "n_layers": 3,
                               "base_delta": 0.5}, True),
    "gray8_multitile": ((96, 64), 8,
                        {"lossless": True, "levels": 2, "tile_size": 64},
                        False),
    "gray16_lossless": ((64, 64), 16, {"lossless": True, "levels": 2},
                        False),
    "gray16_rate": ((64, 64), 16,
                    {"lossless": False, "levels": 2, "rate": 2.0,
                     "n_layers": 3, "base_delta": 0.5 * 256}, False),
}


@pytest.mark.parametrize("case", list(_WHOLE_ENCODES))
def test_whole_encode_matches_python_coder(case, rng, monkeypatch):
    """A whole encode through the native host coder — the packed
    payload, the thread pool, the distortion correction and PCRD —
    writes the same file as the same encode through the pure-Python
    reference coder."""
    shape, bitdepth, fields, rerun = _WHOLE_ENCODES[case]
    img = _photo(rng, shape, bitdepth)
    params = EncodeParams(**fields)

    def encode():
        sink = Metrics()
        monkeypatch.setattr(encoder, "_metrics_sink", sink)
        if rerun:
            _starve_first_floors(monkeypatch)
        data = encoder.encode_jp2(img, bitdepth, params)
        return data, sink

    got, got_sink = encode()
    assert t1_batch.last_native_call["fn"] == "t1_encode_packed"
    before = t1_batch.python_coded_blocks
    monkeypatch.setattr(native, "load", lambda: None)
    ref, ref_sink = encode()
    assert t1_batch.python_coded_blocks > before
    assert got == ref
    reruns = 1 if rerun else 0
    assert got_sink.counters["encode.floor_reruns"] == reruns
    assert ref_sink.counters["encode.floor_reruns"] == reruns


def _dense_specs(rng, n):
    """Blocks heavy enough that the native call takes real wall time."""
    return [((rng.random((64, 64)) * 4096).astype(np.uint32),
             rng.random((64, 64)) < 0.5, "LL", None) for _ in range(n)]


def test_native_call_releases_gil_and_records_pool(rng):
    """The overlap pipeline's whole premise: the ctypes Tier-1 call must
    release the GIL for its duration (CDLL does; PyDLL would not), or
    the 'overlapped' host worker would serialize against device
    dispatch. Proven by running pure-Python work concurrently with a
    native batch: with the GIL released the spinner makes millions of
    iterations; held, it would make a few hundred in the call-boundary
    windows. Also checks the pool-size bookkeeping the call records."""
    import threading

    specs = _dense_specs(rng, 64)
    stop = threading.Event()
    progress = [0]

    def spin():
        while not stop.is_set():
            progress[0] += 1

    spinner = threading.Thread(target=spin)
    spinner.start()
    try:
        before = progress[0]
        t1_batch.encode_blocks(specs)
        during = progress[0] - before
    finally:
        stop.set()
        spinner.join()
    assert during > 50_000, (
        f"only {during} Python iterations ran concurrently with the "
        "native Tier-1 call — the GIL appears held for the call")
    assert t1_batch.last_native_call["fn"] == "t1_encode_blocks"
    assert t1_batch.last_native_call["n_blocks"] == len(specs)
    assert t1_batch.last_native_call["threads"] == \
        t1_batch.default_threads()
    if (os.cpu_count() or 1) > 2:
        assert t1_batch.last_native_call["threads"] > 1, (
            "thread pool pinned to 1 on a multi-core host — Tier-1 "
            "cannot scale past one core")


@pytest.mark.slow
def test_thread_pool_scales_past_one_core(rng, monkeypatch):
    """Wall-clock evidence the pool parallelizes (timing-sensitive, so
    slow-marked): cores-1 threads must beat a deliberately pinned
    single-thread run on a large batch."""
    if (os.cpu_count() or 1) < 3:
        pytest.skip("needs >= 3 cores for a meaningful comparison")
    specs = _dense_specs(rng, 96)
    t1_batch.encode_blocks(specs)       # warm (lib load, allocator)

    def timed():
        t0 = time.perf_counter()
        t1_batch.encode_blocks(specs)
        return time.perf_counter() - t0

    monkeypatch.setenv("BUCKETEER_T1_THREADS", "1")
    serial = min(timed() for _ in range(2))
    monkeypatch.delenv("BUCKETEER_T1_THREADS")
    pooled = min(timed() for _ in range(2))
    assert pooled < serial * 0.8, (
        f"no concurrent speedup: pooled {pooled:.3f}s vs single-thread "
        f"{serial:.3f}s")
