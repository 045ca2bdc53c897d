"""Device CX/D context modeling (codec/cxd.py) vs the reference coder.

The contract under test: the device stripe scan emits *exactly* the
(context, decision) sequence codec/t1.py feeds its MQEncoder — across
band classes, all three passes, the run-length shortcut, sign coding,
partial blocks and bit-plane floors — so replaying the stream through
the host MQ coder (native t1_encode_cxd or the Python fallback) yields
byte-identical block data, identical truncation points, and
bit-identical distortion values. The tensor codec's ``replay`` backend
runs this path; image encodes take the packed host coder.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bucketeer_tpu import native
from bucketeer_tpu.codec import cxd, rate as rate_mod, t1_batch

P_TEST = 5          # one compiled scan shared by every unit trial


@pytest.fixture(scope="module")
def cxd_single():
    return jax.jit(partial(cxd._cxd_single, P_TEST, 0))


def _random_block(rng, h, w, max_bits=P_TEST, density=0.3):
    mags = ((rng.random((h, w)) < density)
            * rng.integers(0, 1 << max_bits, size=(h, w))).astype(
        np.uint32)
    negs = rng.random((h, w)) < 0.5
    return mags, negs


def _run_device(cxd_single, mags, negs, band, floor):
    h, w = mags.shape
    coeffs = np.zeros((64, 64), np.int32)
    coeffs[:h, :w] = mags.astype(np.int64) * np.where(negs, -1, 1)
    nbp = int(mags.max()).bit_length()
    buf, counts, dh, dl, cur = cxd_single(
        jnp.asarray(coeffs), jnp.int32(nbp), jnp.int32(floor),
        jnp.int32(cxd.BAND_CLS[band]), jnp.int32(h), jnp.int32(w))
    return (np.asarray(buf), np.asarray(counts), np.asarray(dh),
            np.asarray(dl), int(cur), nbp)


def test_streams_match_reference_across_bands_and_floors(rng, cxd_single):
    """Property test: device symbol streams, pass boundaries and
    distortion values equal the recording reference for random blocks in
    every band class, with and without floors, including partial blocks
    and blocks with fewer planes than the scan capacity."""
    cases = [(cxd_single, rng, band, floor, hw)
             for band in ("LL", "HL", "LH", "HH")
             for floor, hw in ((0, (64, 64)), (2, (37, 11)))]
    cases.append((cxd_single, rng, "LL", 0, (5, 64)))
    for args in cases:
        _check_one(*args)
    # Fewer coded planes than capacity: plane masking above the MSB.
    mags, negs = _random_block(rng, 16, 16, max_bits=2)
    _check_block(cxd_single, mags, negs, "HH", 0)


def _check_one(cxd_single, rng, band, floor, hw):
    mags, negs = _random_block(rng, *hw)
    mags.flat[0] = (1 << P_TEST) - 1       # pin nbp == P_TEST
    _check_block(cxd_single, mags, negs, band, floor)


def _check_block(cxd_single, mags, negs, band, floor):
    # The packed path truncates magnitude bits below the floor before
    # estimating distortions; mirror that for the reference.
    mags_f = (mags >> floor) << floor
    ref_blk, ref_syms, ref_bounds = cxd.reference_cxd(
        mags_f, negs, band, floor)
    buf, counts, dh, dl, cur, nbp = _run_device(
        cxd_single, mags, negs, band, floor)
    assert cur == len(ref_syms), (band, floor)
    np.testing.assert_array_equal(buf[:cur], ref_syms)
    assert cur <= cxd.max_syms(P_TEST)

    offs, types, planes, nsyms, dists, totals = cxd.pass_tables(
        np.array([nbp], np.int32), np.array([floor], np.int32),
        counts[None], dh[None], dl[None])
    np.testing.assert_array_equal(np.cumsum(nsyms), ref_bounds)
    ref_d = np.array([p.dist_reduction for p in ref_blk.passes])
    np.testing.assert_array_equal(dists, ref_d)   # bit-identical f64

    replayed = cxd.replay_block(buf[:cur], nbp, len(types), types,
                                planes, nsyms, dists)
    assert replayed.data == ref_blk.data
    for got, want in zip(replayed.passes, ref_blk.passes):
        assert got.cum_length == want.cum_length
        assert got.pass_type == want.pass_type
        assert got.bitplane == want.bitplane


def test_eff_group_partitioner():
    """The Mb clamp's launch planner: dead blocks (all-zero, or floored
    away) join no group, live blocks bucket by pow-2 of their realized
    plane depth, and tiny groups merge into the next larger bucket."""
    nbps = np.array([0, 5, 5, 1, 3, 3, 3, 3, 8], np.int32)
    floors = np.array([0, 5, 1, 0, 0, 0, 0, 0, 0], np.int32)
    groups, eff = cxd._eff_groups(nbps, floors)
    np.testing.assert_array_equal(eff, [0, 0, 4, 1, 3, 3, 3, 3, 8])
    by_l = {l: list(i) for l, i in groups}
    covered = sorted(i for idxs in by_l.values() for i in idxs)
    assert covered == [2, 3, 4, 5, 6, 7, 8]     # 0 and 1 are dead
    # eff 1..8 all land in the smallest launch bucket.
    assert by_l == {8: [2, 3, 4, 5, 6, 7, 8]}
    for l_val, idxs in groups:
        assert l_val in cxd.LAUNCH_PLANE_BUCKETS
        assert len(idxs) >= cxd.GROUP_MIN_BLOCKS or l_val == max(by_l)
        assert all(eff[i] <= l_val for i in idxs)
    # A deeper block splits off its own bucket once populated.
    nbps2 = np.array([3, 3, 3, 3, 12, 12, 12, 12], np.int32)
    groups2, eff2 = cxd._eff_groups(nbps2, np.zeros(8, np.int32))
    assert {l: list(i) for l, i in groups2} == \
        {8: [0, 1, 2, 3], 16: [4, 5, 6, 7]}
    # The bucket mapper itself.
    assert [cxd._launch_bucket(e) for e in (1, 4, 5, 9, 17)] == \
        [8, 8, 8, 16, 32]
    with pytest.raises(ValueError):
        cxd._launch_bucket(33)


def test_sparse_mb_clamped_chunk_byte_identical(rng, cxd_single):
    """Mb-clamped sparse cases through the full grouped chunk path:
    all-zero blocks and floored-dead blocks launch nothing, a
    single-significant-coefficient block rides the smallest bucket,
    and every live block replays byte-identical to the reference."""
    n = 6
    blocks = np.zeros((n, 64, 64), np.int32)
    metas = []
    bands = ["LL", "HH", "HL", "LH", "LL", "HH"]
    for i, maxb in enumerate((P_TEST, 1, 2, P_TEST, P_TEST, 3)):
        h = int(rng.integers(1, 65))
        w = int(rng.integers(1, 65))
        mags, negs = _random_block(rng, h, w, max_bits=maxb)
        if i == 1:
            mags[:] = 0
            mags[h // 2, w // 2] = 1        # single significant sample
        if i == 4:
            mags[:] = 0                     # all-zero block
        blocks[i, :h, :w] = mags.astype(np.int64) * np.where(negs, -1, 1)
        metas.append((mags, negs, bands[i], h, w))
    nbps = np.array([int(m.max()).bit_length() for m, *_ in metas],
                    np.int32)
    floors = np.array([0, 0, 0, P_TEST, 0, 1], np.int32)  # 3: dead
    hs = np.array([m[3] for m in metas], np.int32)
    ws = np.array([m[4] for m in metas], np.int32)
    groups, eff = cxd._eff_groups(nbps, floors)
    grouped = {i for _, idxs in groups for i in idxs}
    assert 3 not in grouped and 4 not in grouped    # zero trips
    streams = cxd.run_cxd(jnp.asarray(blocks), nbps, floors, bands,
                          hs, ws, P_TEST, 0)
    got = t1_batch.encode_cxd(streams)
    for i, (mags, negs, band, h, w) in enumerate(metas):
        floor = int(floors[i])
        if nbps[i] <= floor:
            assert got[i].data == b"" and not got[i].passes
            continue
        mags_f = (mags >> floor) << floor
        ref_blk, _, _ = cxd.reference_cxd(mags_f, negs, band, floor)
        assert got[i].data == ref_blk.data, f"block {i}"
        for gp, rp in zip(got[i].passes, ref_blk.passes):
            assert gp.cum_length == rp.cum_length
            assert gp.dist_reduction == rp.dist_reduction


def test_pack6_roundtrip(rng):
    syms = rng.integers(0, 64, size=512).astype(np.uint8)
    packed = np.asarray(cxd.pack6(jnp.asarray(syms[None])))[0]
    assert packed.nbytes == 384                  # ~6 bits/symbol
    np.testing.assert_array_equal(cxd.unpack6(packed, 500), syms[:500])


def test_run_cxd_and_native_replay_match_reference(rng):
    """The full chunk path: run_cxd (device program + pass tables +
    row-granular symbol fetch) then t1_batch.encode_cxd — native thread
    pool when available — equals the reference coder block for block."""
    n = 5
    blocks = np.zeros((n, 64, 64), np.int32)
    metas = []
    for i in range(n):
        h = int(rng.integers(1, 65))
        w = int(rng.integers(1, 65))
        mags, negs = _random_block(rng, h, w)
        if i == 3:
            mags[:] = 0                         # all-zero block
        blocks[i, :h, :w] = mags.astype(np.int64) * np.where(negs, -1, 1)
        metas.append((mags, negs, ["LL", "HL", "LH", "HH", "LL"][i], h, w))
    nbps = np.array([int(m.max()).bit_length() for m, *_ in metas],
                    np.int32)
    floors = np.array([0, 1, 0, 0, 5], np.int32)  # block 4: floor >= nbp
    streams = cxd.run_cxd(jnp.asarray(blocks), nbps, floors,
                          [b for *_, b, _, _ in metas],
                          np.array([m[3] for m in metas], np.int32),
                          np.array([m[4] for m in metas], np.int32),
                          P_TEST, 0)
    got = t1_batch.encode_cxd(streams)
    for i, (mags, negs, band, h, w) in enumerate(metas):
        floor = int(floors[i])
        if nbps[i] <= floor:
            assert got[i].data == b"" and got[i].n_bitplanes == 0
            continue
        mags_f = (mags >> floor) << floor
        ref_blk, _, _ = cxd.reference_cxd(mags_f, negs, band, floor)
        assert got[i].data == ref_blk.data, f"block {i}"
        assert got[i].n_bitplanes == ref_blk.n_bitplanes
        assert len(got[i].passes) == len(ref_blk.passes)
        for gp, rp in zip(got[i].passes, ref_blk.passes):
            assert gp.cum_length == rp.cum_length
            assert gp.dist_reduction == rp.dist_reduction


def test_python_fallback_replay_matches(rng, monkeypatch):
    mags, negs = _random_block(rng, 33, 29)
    blocks = np.zeros((1, 64, 64), np.int32)
    blocks[0, :33, :29] = mags.astype(np.int64) * np.where(negs, -1, 1)
    nbps = np.array([int(mags.max()).bit_length()], np.int32)
    streams = cxd.run_cxd(jnp.asarray(blocks), nbps,
                          np.zeros(1, np.int32), ["HH"],
                          np.array([33], np.int32),
                          np.array([29], np.int32), P_TEST, 0)
    monkeypatch.setattr(native, "load", lambda: None)
    got = t1_batch.encode_cxd(streams)
    ref_blk, _, _ = cxd.reference_cxd(mags, negs, "HH", 0)
    assert got[0].data == ref_blk.data


def test_pallas_kernel_matches_jnp_scan(rng):
    """The Pallas kernel (interpret mode on CPU) and the vmapped scan
    share one scan body; prove their outputs are bit-identical anyway —
    buffer, counts, cursors, distortions. Kept at L=2: interpret mode
    executes every trip through the Python interpreter, so trip count
    is this test's wall clock."""
    from bucketeer_tpu.codec.pallas.cxd_scan import cxd_pallas

    L = 2
    n = 2
    blocks = np.zeros((n, 64, 64), np.int32)
    for i in range(n):
        mags, negs = _random_block(rng, 64, 64, max_bits=L, density=0.2)
        blocks[i] = mags.astype(np.int64) * np.where(negs, -1, 1)
    nbps = np.array([int(np.abs(blocks[i]).max()).bit_length()
                     for i in range(n)], np.int32)
    floors = np.array([0, 1], np.int32)
    cls = np.array([0, 2], np.int32)
    hw = np.full(n, 64, np.int32)
    args = (jnp.int32(0), jnp.asarray(blocks), jnp.asarray(nbps),
            jnp.asarray(floors), jnp.asarray(cls), jnp.asarray(hw),
            jnp.asarray(hw))
    ref = [np.asarray(a)
           for a in jax.jit(cxd._scan_impl(L, False, False))(*args)]
    got = [np.asarray(a) for a in cxd_pallas(L, *args, interpret=True)]
    for g, r in zip(got, ref):
        np.testing.assert_array_equal(g, r)


# --- floor estimator regression (ADVICE r5 #4) --------------------------

def test_estimate_floors_never_zeroes_live_block():
    """A block whose top plane clears the loose slope threshold must
    keep at least its MSB plane instead of being dropped outright."""
    n, P = 3, 4
    nbps = np.array([4, 4, 4], np.int32)
    newsig = np.zeros((n, P), np.int64)
    sigd = np.zeros((n, P), np.float64)
    newsig[:, 3] = 8
    # Block 0 dominates (sets the threshold); block 1's top plane is
    # ~8x cheaper (within the 16x slack); block 2 is noise, far below.
    sigd[0, :] = [1.0, 10.0, 100.0, 1e6]
    sigd[1, 3] = 1e6 / 8.0
    sigd[2, 3] = 1e-3
    refd = np.zeros((n, P), np.float64)
    weights = np.ones(n)
    n_samples = np.full(n, 4096)
    floors, lam = rate_mod.estimate_floors(
        nbps, newsig, sigd, refd, weights, n_samples,
        target_bytes=20.0, margin=1.0)
    assert lam > 0
    assert floors[0] < nbps[0]
    assert floors[1] == nbps[1] - 1, (
        f"live block fully zeroed: floors={floors} lam={lam}")
    assert floors[2] == nbps[2]


def test_cut_slope_detects_floor_violation():
    """cut_slope returns the realized PCRD cut; a cut far below the
    floor threshold is the retry trigger."""
    from bucketeer_tpu.codec import t1

    blocks = []
    for lens, dists in (((10, 20), (100.0, 110.0)),
                        ((8, 30), (80.0, 84.0))):
        blk = t1.CodedBlock(b"x" * lens[-1], 5)
        blk.passes = [t1.PassInfo(2, 4, lens[0], dists[0]),
                      t1.PassInfo(2, 3, lens[1], dists[1] - dists[0])]
        blocks.append(blk)
    tight = rate_mod.cut_slope(blocks, [1.0, 1.0], 12.0)
    loose = rate_mod.cut_slope(blocks, [1.0, 1.0], 1000.0)
    assert tight > loose >= 0.0
