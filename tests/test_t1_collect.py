"""Host Tier-1 results through one native copy: ``t1_batch._collect``
builds the same code-blocks as a block-by-block, pass-by-pass reference
(the shape the collector had before it copied the whole result at once
and corrected the distortions on flat arrays), and whole encodes stay
byte-identical with the reference patched in.
"""
import copy

import numpy as np
import pytest

from bucketeer_tpu import native
from bucketeer_tpu.codec import encoder, t1, t1_batch
from bucketeer_tpu.codec.encoder import EncodeParams

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native T1 unavailable (no g++?)")

P = 16      # packed plane capacity of the synthetic chunks


def _scalar_correct(blocks: list, exact) -> None:
    """The per-pass distortion correction, one numpy scalar at a time."""
    sigd, refd = exact
    for bi, blk in enumerate(blocks):
        if not blk.passes:
            continue
        est_sig = [0.0] * sigd.shape[1]
        est_ref = [0.0] * sigd.shape[1]
        for info in blk.passes:
            if info.pass_type == 1:
                est_ref[info.bitplane] += info.dist_reduction
            else:
                est_sig[info.bitplane] += info.dist_reduction
        for info in blk.passes:
            p = info.bitplane
            est = est_ref[p] if info.pass_type == 1 else est_sig[p]
            ex = refd[bi, p] if info.pass_type == 1 else sigd[bi, p]
            if est > 0.0 and ex >= 0.0:
                info.dist_reduction *= ex / est


def _scalar_collect(lib, handle, n: int, exact=None) -> list:
    """Reference collector: per-block arrays and per-pass objects built
    one scalar at a time, then :func:`_scalar_correct`."""
    try:
        nbps = np.zeros(n, dtype=np.int32)
        npasses = np.zeros(n, dtype=np.int32)
        nbytes = np.zeros(n, dtype=np.int64)
        lib.t1_block_sizes(handle, nbps.ctypes.data, npasses.ctypes.data,
                           nbytes.ctypes.data)
        data = np.zeros(max(int(nbytes.sum()), 1), dtype=np.uint8)
        k = max(int(npasses.sum()), 1)
        ptype = np.zeros(k, dtype=np.int32)
        pplane = np.zeros(k, dtype=np.int32)
        plen = np.zeros(k, dtype=np.int64)
        pdist = np.zeros(k, dtype=np.float64)
        lib.t1_result_copy(handle, data.ctypes.data, ptype.ctypes.data,
                           pplane.ctypes.data, plen.ctypes.data,
                           pdist.ctypes.data)
    finally:
        lib.t1_result_free(handle)
    out, b0, k0 = [], 0, 0
    for i in range(n):
        np_i, nb_i = int(npasses[i]), int(nbytes[i])
        passes = [t1.PassInfo(int(ptype[k0 + j]), int(pplane[k0 + j]),
                              int(plen[k0 + j]), float(pdist[k0 + j]))
                  for j in range(np_i)]
        out.append(t1.CodedBlock(bytes(data[b0:b0 + nb_i].tobytes()),
                                 int(nbps[i]), passes))
        b0 += nb_i
        k0 += np_i
    if exact is not None:
        _scalar_correct(out, exact)
    return out


def _packed_chunk(rng, n: int, with_floor: bool):
    """A seeded chunk in the device front-end's packed layout: per block a
    sign bitmap row, then one 512-byte bitmap row a plane from nbps-1
    down to the floor. Every fifth block has no plane and every seventh
    sits under its floor, so empty blocks lie between coded ones."""
    rows, offsets = [], [0]
    nbps = np.zeros(n, np.int32)
    floors = np.zeros(n, np.int32)
    hs = rng.integers(1, 65, n).astype(np.int32)
    ws = rng.integers(1, 65, n).astype(np.int32)
    bands = [["LL", "HL", "LH", "HH"][i % 4] for i in range(n)]
    for i in range(n):
        h, w = int(hs[i]), int(ws[i])
        nbp = 0 if i % 5 == 4 else int(rng.integers(1, P + 1))
        flo = int(rng.integers(0, nbp)) if with_floor and nbp > 1 else 0
        if i % 7 == 6:
            flo = nbp            # dead block under the floor: no rows
        nbps[i], floors[i] = nbp, flo
        if nbp <= flo:
            offsets.append(offsets[-1])
            continue
        density = rng.choice([0.05, 0.4, 0.9])
        mags = np.zeros((64, 64), np.uint32)
        mags[:h, :w] = ((rng.random((h, w)) < density)
                        * rng.integers(0, 1 << nbp, (h, w)))
        negs = np.zeros((64, 64), np.uint8)
        negs[:h, :w] = rng.random((h, w)) < 0.5
        rows.append(np.packbits(negs, axis=1, bitorder="little").ravel())
        for p in range(nbp - 1, flo - 1, -1):
            rows.append(np.packbits(((mags >> p) & 1).astype(np.uint8),
                                    axis=1, bitorder="little").ravel())
        offsets.append(offsets[-1] + nbp - flo + 1)
    payload = (np.stack(rows) if rows
               else np.zeros((0, 512), np.uint8)).astype(np.uint8)
    return (payload, np.asarray(offsets, np.int64), nbps, floors, hs, ws,
            bands)


def _exact(rng, n: int):
    """Seeded per-plane exact sums in the device's float32, with negative
    and zero entries so both skip branches of the correction run."""
    def one():
        a = rng.gamma(2.0, 50.0, (n, P)).astype(np.float32)
        a[rng.random((n, P)) < 0.1] = -1.0
        a[rng.random((n, P)) < 0.1] = 0.0
        return a
    return one(), one()


def _assert_same_blocks(got: list, ref: list) -> None:
    assert len(got) == len(ref)
    for g, r in zip(got, ref):
        assert g.data == r.data
        assert g.n_bitplanes == r.n_bitplanes
        assert len(g.passes) == len(r.passes)
        for gp, rp in zip(g.passes, r.passes):
            assert gp.pass_type == rp.pass_type
            assert gp.bitplane == rp.bitplane
            assert gp.cum_length == rp.cum_length
            assert float(gp.dist_reduction) == float(rp.dist_reduction)


@pytest.mark.parametrize("lossy,with_floor", [
    (False, False), (False, True), (True, False), (True, True)],
    ids=["lossless", "lossless_floor", "lossy", "lossy_floor"])
def test_flat_collect_matches_scalar_reference(lossy, with_floor,
                                               monkeypatch):
    rng = np.random.default_rng([27, lossy, with_floor])
    n = 60
    chunk = _packed_chunk(rng, n, with_floor)
    exact = _exact(rng, n) if lossy else None
    got = t1_batch.encode_packed(*chunk, exact=exact)
    monkeypatch.setattr(t1_batch, "_collect", _scalar_collect)
    ref = t1_batch.encode_packed(*chunk, exact=exact)
    _assert_same_blocks(got, ref)
    assert any(not b.passes and b.data == b"" for b in got)
    assert sum(len(b.passes) for b in got) > n
    if lossy:
        # The correction moved some distortions and skipped others.
        plain = t1_batch.encode_packed(*chunk)
        moved = [gp.dist_reduction != pp.dist_reduction
                 for g, p in zip(got, plain)
                 for gp, pp in zip(g.passes, p.passes)]
        assert any(moved) and not all(moved)


def test_correcting_built_blocks_matches_scalar_reference():
    """The Python fallback of encode_packed corrects blocks that are
    already objects."""
    rng = np.random.default_rng(2727)
    n = 40
    chunk = _packed_chunk(rng, n, with_floor=True)
    exact = _exact(rng, n)
    blocks = t1_batch.encode_packed(*chunk)
    ref = copy.deepcopy(blocks)
    t1_batch.correct_distortions(blocks, exact)
    _scalar_correct(ref, exact)
    _assert_same_blocks(blocks, ref)


@pytest.mark.parametrize("lossy", [False, True],
                         ids=["lossless", "lossy"])
def test_native_copy_matches_python_coder(lossy, monkeypatch):
    """Ties the one copy to an independent coder: the pure-Python
    fallback (which corrects the same way when given the sums)."""
    rng = np.random.default_rng([2700, lossy])
    n = 14
    chunk = _packed_chunk(rng, n, with_floor=True)
    exact = _exact(rng, n) if lossy else None
    got = t1_batch.encode_packed(*chunk, exact=exact)
    monkeypatch.setattr(native, "load", lambda: None)
    ref = t1_batch.encode_packed(*chunk, exact=exact)
    assert len(got) == len(ref) == n
    for g, r in zip(got, ref):
        assert (g.data, g.n_bitplanes) == (r.data, r.n_bitplanes)
        assert ([(p.pass_type, p.bitplane, p.cum_length) for p in g.passes]
                == [(p.pass_type, p.bitplane, p.cum_length)
                    for p in r.passes])
        # The coders' estimates agree to rounding; a corrected pass
        # goes through float32, so one ulp there.
        assert [p.dist_reduction for p in g.passes] == pytest.approx(
            [p.dist_reduction for p in r.passes], rel=1e-6, abs=1e-9)


def test_result_export_is_one_copy_at_abi_6():
    lib = native.load()
    assert native._ABI_VERSION == 6
    assert lib.t1_abi_version() == native._ABI_VERSION
    assert hasattr(lib, "t1_result_copy")
    assert not hasattr(lib, "t1_block_get")


def _rgb(h, w):
    rng = np.random.default_rng(512)
    y, x = np.mgrid[0:h, 0:w].astype(np.float32)
    base = 128 + 80 * np.sin(x / 37.0) * np.cos(y / 23.0)
    return np.clip(base[..., None] + rng.normal(0, 12, (h, w, 3)),
                   0, 255).astype(np.uint8)


def _gray16(h, w):
    rng = np.random.default_rng(16)
    y, x = np.mgrid[0:h, 0:w].astype(np.float32)
    base = 32768 + 18000 * np.sin(x / 41.0) * np.cos(y / 29.0)
    return np.clip(base + rng.normal(0, 300, (h, w)), 0,
                   65535).astype(np.uint16)


@pytest.mark.parametrize("case", ["rgb8_lossy", "gray16_lossless"])
def test_whole_encode_byte_identical_to_scalar_reference(case,
                                                         monkeypatch):
    if case == "rgb8_lossy":
        img, bitdepth = _rgb(512, 512), 8
        params = EncodeParams.kakadu_recipe(False, 3.0)
    else:
        img, bitdepth = _gray16(512, 512), 16
        params = EncodeParams.kakadu_recipe(True)
    with monkeypatch.context() as m:
        m.setattr(t1_batch, "_collect", _scalar_collect)
        ref = encoder.encode_jp2(img, bitdepth, params)
    assert encoder.encode_jp2(img, bitdepth, params) == ref
