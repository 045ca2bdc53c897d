"""Device-side EBCOT Tier-1: stripe-parallel CX/D context modeling and
the MQ arithmetic coder on the TPU.

The host Tier-1 coder (native/t1.cpp) used to redo the full Annex D
context modeling for every bit-plane of every code-block. This module
moves the whole of Tier-1 onto the device:

- **CX/D scan** — per block, the exact ordered (context, decision)
  symbol sequence the MQ coder consumes, plus per-pass symbol counts
  (PCRD truncation boundaries) and exact per-pass distortions.
- **MQ coder** (:func:`run_device_mq`) — a byte-emitting scan
  replicating the host ``MQEncoder`` register for register, fused with
  the CX/D scan into one device program (:func:`fused_program`) so the
  symbol buffer never round-trips HBM.

Scan structure (the stripe-parallel trip model, this PR): the scan is
*relative to each block's MSB* — an outer loop over plane offsets
``off = 0..L-1`` (``L`` = the launch group's Mb-clamped plane budget,
``off`` maps to absolute plane ``p = nbp-1-off`` per block) around
three *specialized* pass scans, each processing ``COLS_PER_TRIP``
adjacent stripe columns per trip:

- ``off == 0`` is peeled: the first coded plane runs only its cleanup
  pass, so the sigprop/magref trips for it simply do not exist;
- sigprop / cleanup trips run their columns in coding order inside the
  trip (the significance wavefront is sequential by construction) but
  share one wide state slice and emit all symbols through one batched
  scatter per trip;
- magref never changes significance state, so its whole trip
  vectorizes across the ``4 x COLS_PER_TRIP`` samples.

Trip counts per launch: ``COL_TRIPS + (L-1) * 3 * COL_TRIPS`` versus
the old ``P * 3 * COLS_PER_PLANE`` — a >= 4x static cut at equal
output (the graftcost manifest pins it), on top of which the Mb
clamping makes ``L`` the *realized* plane depth, not the chunk-wide
capacity: :func:`run_cxd` / :func:`run_device_mq` partition each
chunk's blocks into LAUNCH_PLANE_BUCKETS of ``nbp - floor`` (dead blocks —
all-zero, or floored away — never launch at all).

Byte parity is the contract: the symbol sequence equals the one
codec/t1.py's reference coder feeds its MQEncoder (tests/test_cxd.py
proves this with a recording coder), so replaying it yields
byte-identical block streams and identical truncation lengths.

Distortion exactness: PCRD byte-parity with the legacy packed path also
requires bit-identical per-pass distortion values. The native packed
coder accumulates integer-valued midpoint terms in float64; float64 is
unavailable on device, so the scan accumulates ``4 x dist`` (always an
integer) as an unevaluated double-float pair — Dekker two-product /
Knuth two-sum — in the reference's accumulation order, which represents
integer sums exactly to ~2^48. The host reconstitutes ``(hi + lo) / 4``
in float64 and lands on the same number the native coder would have
produced.

MQ coding: the per-symbol scan is restructured around
``MQ_UNROLL``-symbol trips. Renormalization computes its shift count
arithmetically (15 comparisons instead of a 15-iteration masked loop)
and performs at most three masked byteouts per symbol — provably
enough: a renorm shifts <= 15 times, the first byteout costs <= 12
shifts of countdown and each later one reloads CT to 7/8. The byte at
``cur - 1`` is carried as a ``pending`` register (the "outstanding
byte" convention), so byteout needs no buffer read and exactly one
buffer write. Byte identity with the host ``MQEncoder`` — stuffing,
the 0xFF carry paths, flush, the trailing-0xFF drop and the per-pass
``truncation_length`` snapshots — is the contract
(tests/test_mq_device.py).
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from functools import lru_cache, partial
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..analysis import graftcost, retrace
from ..config import truthy as cfg_truthy
from .mq import CTX_RL, CTX_UNIFORM, MQEncoder, QE_TABLE
from .pipeline import donate_argnums_if_supported
from .t1 import _SC, _ZC_HH, _ZC_LL_LH, BAND_CLS

CBLK = 64
STRIPES = CBLK // 4
COLS_PER_PLANE = STRIPES * CBLK          # stripe columns per pass
COLS_PER_TRIP = 4                        # stripe columns per scan trip
COL_TRIPS = COLS_PER_PLANE // COLS_PER_TRIP
SYMS_PER_ROW = 512                       # fetch granularity (symbols)
PACKED_ROW_BYTES = SYMS_PER_ROW * 3 // 4  # 6 bits/symbol -> 384 bytes

# Blocks per launch group below which a group merges into the next
# larger plane bucket instead of paying its own dispatch.
GROUP_MIN_BLOCKS = 4

# Allowed launch plane budgets. Coarser than pow-2 on purpose: every
# distinct L compiles its own scan programs (~20 s of XLA on CPU), so
# the bucket set bounds the fleet of compiled variants per process at
# three per program kind — while the *relative* plane indexing still
# starts every block at its own MSB, so the coarseness only costs
# masked trailing offsets, never re-scanned empty top planes.
# int32 magnitudes cap nbp at 31, so 32 covers everything.
LAUNCH_PLANE_BUCKETS = (8, 16, 32)


def _launch_bucket(eff: int) -> int:
    for b in LAUNCH_PLANE_BUCKETS:
        if b >= eff:
            return b
    raise ValueError(f"plane depth {eff} exceeds the largest launch "
                     f"bucket {LAUNCH_PLANE_BUCKETS[-1]}")


def _zc_stack() -> np.ndarray:
    hl = np.transpose(_ZC_LL_LH, (1, 0, 2))
    return np.stack([_ZC_LL_LH, _ZC_HH, hl]).astype(np.int32)


def _sc_tables():
    ctx = np.zeros((3, 3), dtype=np.int32)
    xor = np.zeros((3, 3), dtype=np.int32)
    for (h, v), (c, x) in _SC.items():
        ctx[h + 1, v + 1] = c
        xor[h + 1, v + 1] = x
    return ctx, xor


def max_syms(L: int) -> int:
    """Static per-block symbol capacity for an ``L``-plane scan: per
    scanned plane, every sample emits at most one decision, a
    run-length shortcut adds at most 2 symbols per stripe column, and
    each sample emits its sign exactly once ever."""
    return L * (CBLK * CBLK + 2 * COLS_PER_PLANE) + CBLK * CBLK


def rows_per_block(L: int) -> int:
    return max_syms(L) // SYMS_PER_ROW


def _pow2ceil(v: int) -> int:
    return 1 << max(0, int(v) - 1).bit_length()


# --- exact double-float accumulation (see module docstring) -------------

def _two_sum(a, b):
    s = a + b
    bb = s - a
    return s, (a - (s - bb)) + (b - bb)


_SPLIT = np.float32(4097.0)      # 2^12 + 1 (Veltkamp)


def _two_prod(a, b):
    p = a * b
    aa = _SPLIT * a
    ahi = aa - (aa - a)
    alo = a - ahi
    bb = _SPLIT * b
    bhi = bb - (bb - b)
    blo = b - bhi
    err = ((ahi * bhi - p) + ahi * blo + alo * bhi) + alo * blo
    return p, err


def _dd_add(dsh, dsl, cond, fa, fb):
    """(dsh, dsl) += fa * fb exactly, masked by ``cond`` — the scalar
    form of the double-float accumulation, applied in the reference
    coder's per-sample order so the represented sum is bit-stable."""
    a = jnp.where(cond, fa, jnp.float32(0.0))
    b = jnp.where(cond, fb, jnp.float32(0.0))
    ph, pe = _two_prod(a, b)
    sh, se = _two_sum(dsh, ph)
    te = dsl + pe + se
    return _two_sum(sh, te)


def _d4_sig(v, p):
    """4 x significance distortion (t1.sig_dist with tv = v) as two exact
    int-valued float32 factors: D4 = A * (4v - A), A = 2*(vb + 2^(p-1))."""
    a = ((v >> p) << (p + 1)) + (1 << p)
    return a.astype(jnp.float32), (4 * v - a).astype(jnp.float32)


def _d4_ref(v, p):
    """4 x refinement distortion (t1.ref_dist with tv = v):
    D4 = (C - B) * (4v - B - C) with B = 2*r1, C = 2*r0."""
    b = ((v >> (p + 1)) << (p + 2)) + (1 << (p + 1))
    c = ((v >> p) << (p + 1)) + (1 << p)
    return (c - b).astype(jnp.float32), (4 * v - b - c).astype(jnp.float32)


# --- the specialized stripe-trip steps ----------------------------------

def _decode_tau(tau):
    """Flat trip index -> (stripe_y0, first column): stripes top-down,
    COLS_PER_TRIP-column groups left to right within the stripe —
    coding order, shared arithmetic between the jnp and Pallas paths."""
    per_row = CBLK // COLS_PER_TRIP
    return (tau // per_row) * 4, (tau % per_row) * COLS_PER_TRIP


def _flush_emits(buf, cur, ems, msym, batch_emit):
    """Write an ordered list of masked symbol emissions.

    ``ems``: [(cond bool scalar, sym int32 scalar)] in coding order.
    The batched form computes every slot's cursor position with one
    cumulative sum and lands the whole trip's symbols in a single
    scatter (dead slots drop at index ``msym``); the scalar form
    (``batch_emit=False``, the Pallas kernels) replays the same
    positions as per-slot dynamic stores."""
    conds = jnp.stack([c.astype(jnp.int32) for c, _ in ems])
    syms = jnp.stack([s for _, s in ems]).astype(jnp.int32)
    incl = jnp.cumsum(conds)
    pos = cur + incl - conds                 # exclusive prefix
    if batch_emit:
        idxv = jnp.where(conds == 1, pos, msym)
        buf = buf.at[idxv].set(syms.astype(jnp.uint8), mode="drop")
    else:
        for k in range(len(ems)):
            buf = buf.at[jnp.where(conds[k] == 1, pos[k], msym)].set(
                syms[k].astype(jnp.uint8), mode="drop")
    return buf, cur + incl[-1]


def _make_steps(L, idx, neg, nbp, floor, cls, h, w, tables=None,
                batch_emit=True):
    """Build the three specialized pass steps for one block.

    ``idx``/``neg``: (64, 64) int32 floored magnitude indices and sign
    bits; ``nbp``/``floor``/``cls``/``h``/``w``: scalars. Each returned
    ``step(p, valid, carry, tau)`` processes one trip of
    ``COLS_PER_TRIP`` adjacent stripe columns of one pass at absolute
    plane ``p`` (masked dead by ``valid``), and is shared verbatim
    between the vmapped jnp path and the Pallas kernels
    (``batch_emit=False`` swaps the one-scatter emission for per-slot
    stores — same positions, same bytes). ``tables``: optional
    (zc (3,3,3,5), sc_ctx (3,3), sc_xor (3,3)) int32 arrays — the
    Pallas kernels pass them as kernel inputs (kernels cannot capture
    array constants); None embeds them.

    Inner carry: (chi (66,66) int32 zero-padded sign/significance
    state, pi (64,64) int32, ref (64,64) int32, cursor int32,
    buf (max_syms,) uint8, dsh/dsl float32 double-float pass-distortion
    accumulators).
    """
    if tables is None:
        sc_c, sc_x = _sc_tables()
        tables = (jnp.asarray(_zc_stack()), jnp.asarray(sc_c),
                  jnp.asarray(sc_x))
    zc, sc_ctx, sc_xor = tables
    zcf = zc.reshape(3, 45)[cls]             # this block's flat ZC table
    scf_c = sc_ctx.reshape(9)
    scf_x = sc_xor.reshape(9)
    msym = max_syms(L)
    W = COLS_PER_TRIP

    def zc_ctx(sh, sv, sd):
        return zcf[sh * 15 + sv * 5 + sd]

    def sign_of(hsum, vsum, neg_i):
        i9 = (jnp.clip(hsum, -1, 1) + 1) * 3 + (jnp.clip(vsum, -1, 1) + 1)
        return scf_c[i9], neg_i ^ scf_x[i9]

    def slices(chi, pi, ref, y0, x0, p):
        patch = lax.dynamic_slice(chi, (y0, x0), (6, W + 2))
        pi_w = lax.dynamic_slice(pi, (y0, x0), (4, W))
        ref_w = lax.dynamic_slice(ref, (y0, x0), (4, W))
        v_w = lax.dynamic_slice(idx, (y0, x0), (4, W))
        n_w = lax.dynamic_slice(neg, (y0, x0), (4, W))
        return patch, pi_w, ref_w, v_w, n_w, (v_w >> p) & 1

    def nbr(patch, i, j):
        """Neighbor state of sample (i) in wide-patch column (j):
        (h-count, v-count, d-count, signed h-sum, signed v-sum)."""
        l0, l1, l2 = patch[i, j], patch[i + 1, j], patch[i + 2, j]
        r0, r1, r2 = (patch[i, j + 2], patch[i + 1, j + 2],
                      patch[i + 2, j + 2])
        up, dn = patch[i, j + 1], patch[i + 2, j + 1]
        nz = lambda v: (v != 0).astype(jnp.int32)   # noqa: E731
        return (nz(l1) + nz(r1), nz(up) + nz(dn),
                nz(l0) + nz(l2) + nz(r0) + nz(r2), l1 + r1, up + dn)

    def sig_step(p, valid, carry, tau):
        chi, pi, ref, cur, buf, dsh, dsl = carry
        y0, x0 = _decode_tau(tau)
        patch, pi_w, ref_w, v_w, n_w, bit_w = slices(chi, pi, ref,
                                                     y0, x0, p)
        ems = []
        pi_cols = []
        for j in range(W):
            live = valid & (x0 + j < w) & (y0 < h)
            pij = []
            for i in range(4):
                samp_in = live & (y0 + i < h)
                sh, sv, sd, hs_, vs_ = nbr(patch, i, j)
                sig_i = patch[i + 1, j + 1] != 0
                sp = samp_in & ~sig_i & ((sh + sv + sd) > 0)
                ems.append((sp, zc_ctx(sh, sv, sd) | (bit_w[i, j] << 5)))
                newsig = sp & (bit_w[i, j] == 1)
                pij.append(jnp.where(sp, 1, pi_w[i, j]))
                patch = patch.at[i + 1, j + 1].set(
                    jnp.where(newsig, 1 - 2 * n_w[i, j],
                              patch[i + 1, j + 1]))
                fa, fb = _d4_sig(v_w[i, j], p)
                dsh, dsl = _dd_add(dsh, dsl, newsig, fa, fb)
                sctx, sd_ = sign_of(hs_, vs_, n_w[i, j])
                ems.append((newsig, sctx | (sd_ << 5)))
            pi_cols.append(jnp.stack(pij))
        buf, cur = _flush_emits(buf, cur, ems, msym, batch_emit)
        chi = lax.dynamic_update_slice(chi, patch[1:5, 1:1 + W],
                                       (y0 + 1, x0 + 1))
        pi = lax.dynamic_update_slice(pi, jnp.stack(pi_cols, axis=1),
                                      (y0, x0))
        return chi, pi, ref, cur, buf, dsh, dsl

    def mag_step(p, valid, carry, tau):
        # Magref never changes significance or pi state, so the whole
        # trip vectorizes: contexts and refine masks for all 4 x W
        # samples come from pass-start state in one shot.
        chi, pi, ref, cur, buf, dsh, dsl = carry
        y0, x0 = _decode_tau(tau)
        patch, pi_w, ref_w, v_w, n_w, bit_w = slices(chi, pi, ref,
                                                     y0, x0, p)
        sig = (patch != 0).astype(jnp.int32)
        sh = sig[1:5, 0:W] + sig[1:5, 2:W + 2]
        sv = sig[0:4, 1:W + 1] + sig[2:6, 1:W + 1]
        sd = (sig[0:4, 0:W] + sig[0:4, 2:W + 2]
              + sig[2:6, 0:W] + sig[2:6, 2:W + 2])
        nz = (sh + sv + sd) > 0
        rows_in = (y0 + jnp.arange(4)) < h
        cols_in = (x0 + jnp.arange(W)) < w
        samp_in = valid & rows_in[:, None] & cols_in[None, :]
        mr = samp_in & (sig[1:5, 1:W + 1] != 0) & (pi_w == 0)
        ctx = jnp.where(ref_w != 0, 16, jnp.where(nz, 15, 14))
        sym = ctx | (bit_w << 5)
        ems = [(mr[i, j], sym[i, j]) for j in range(W) for i in range(4)]
        buf, cur = _flush_emits(buf, cur, ems, msym, batch_emit)
        fa, fb = _d4_ref(v_w, p)
        for j in range(W):
            for i in range(4):
                dsh, dsl = _dd_add(dsh, dsl, mr[i, j], fa[i, j], fb[i, j])
        ref = lax.dynamic_update_slice(ref, jnp.where(mr, 1, ref_w),
                                       (y0, x0))
        return chi, pi, ref, cur, buf, dsh, dsl

    def cln_step(p, valid, carry, tau):
        chi, pi, ref, cur, buf, dsh, dsl = carry
        y0, x0 = _decode_tau(tau)
        patch, pi_w, ref_w, v_w, n_w, bit_w = slices(chi, pi, ref,
                                                     y0, x0, p)
        ems = []
        for j in range(W):
            live = valid & (x0 + j < w) & (y0 < h)
            # Run-length shortcut: the whole stripe must be in extent,
            # uncoded, insignificant, with empty neighborhoods — all
            # judged on column-start state, exactly like the reference.
            emp = live & ((y0 + 3) < h)
            for i in range(4):
                sh, sv, sd, _, _ = nbr(patch, i, j)
                emp = emp & (patch[i + 1, j + 1] == 0) \
                    & (pi_w[i, j] == 0) & ((sh + sv + sd) == 0)
            rl_ok = emp
            b = [bit_w[i, j] for i in range(4)]
            any_run = (b[0] | b[1] | b[2] | b[3]) == 1
            k = jnp.where(b[0] == 1, 0,
                          jnp.where(b[1] == 1, 1,
                                    jnp.where(b[2] == 1, 2, 3)))
            rl1 = rl_ok & any_run
            ems.append((rl_ok, CTX_RL | (any_run.astype(jnp.int32) << 5)))
            ems.append((rl1, CTX_UNIFORM | (((k >> 1) & 1) << 5)))
            ems.append((rl1, CTX_UNIFORM | ((k & 1) << 5)))
            # Sample k becomes significant with no ZC decision: set
            # state, accumulate its distortion, code its sign.
            for i in range(4):
                patch = patch.at[i + 1, j + 1].set(
                    jnp.where(rl1 & (k == i), 1 - 2 * n_w[i, j],
                              patch[i + 1, j + 1]))
            vk = jnp.where(k == 0, v_w[0, j],
                           jnp.where(k == 1, v_w[1, j],
                                     jnp.where(k == 2, v_w[2, j],
                                               v_w[3, j])))
            nk = jnp.where(k == 0, n_w[0, j],
                           jnp.where(k == 1, n_w[1, j],
                                     jnp.where(k == 2, n_w[2, j],
                                               n_w[3, j])))
            fa, fb = _d4_sig(vk, p)
            dsh, dsl = _dd_add(dsh, dsl, rl1, fa, fb)
            hk = vk_ = None
            for i in range(4):
                _, _, _, hs_, vs_ = nbr(patch, i, j)
                hk = hs_ if hk is None else jnp.where(k == i, hs_, hk)
                vk_ = vs_ if vk_ is None else jnp.where(k == i, vs_, vk_)
            sctx, sd_ = sign_of(hk, vk_, nk)
            ems.append((rl1, sctx | (sd_ << 5)))
            for i in range(4):
                samp_in = live & (y0 + i < h)
                sh, sv, sd, hs_, vs_ = nbr(patch, i, j)
                sig_i = patch[i + 1, j + 1] != 0
                rl_skip = rl_ok & (jnp.logical_not(any_run) | (i <= k))
                cl = samp_in & ~sig_i & (pi_w[i, j] == 0) & ~rl_skip
                ems.append((cl, zc_ctx(sh, sv, sd) | (bit_w[i, j] << 5)))
                newsig = cl & (bit_w[i, j] == 1)
                patch = patch.at[i + 1, j + 1].set(
                    jnp.where(newsig, 1 - 2 * n_w[i, j],
                              patch[i + 1, j + 1]))
                fa, fb = _d4_sig(v_w[i, j], p)
                dsh, dsl = _dd_add(dsh, dsl, newsig, fa, fb)
                sctx, sd_ = sign_of(hs_, vs_, n_w[i, j])
                ems.append((newsig, sctx | (sd_ << 5)))
        buf, cur = _flush_emits(buf, cur, ems, msym, batch_emit)
        chi = lax.dynamic_update_slice(chi, patch[1:5, 1:1 + W],
                                       (y0 + 1, x0 + 1))
        return chi, pi, ref, cur, buf, dsh, dsl

    return sig_step, mag_step, cln_step


def init_state(L: int):
    msym = max_syms(L)
    return (jnp.zeros((CBLK + 2, CBLK + 2), jnp.int32),
            jnp.zeros((CBLK, CBLK), jnp.int32),
            jnp.zeros((CBLK, CBLK), jnp.int32),
            jnp.int32(0),
            jnp.zeros((msym,), jnp.uint8),
            jnp.zeros((L, 3), jnp.int32),
            jnp.zeros((L, 3), jnp.float32),
            jnp.zeros((L, 3), jnp.float32))


def _scan_plane(steps, nbp, floor, state, off, first):
    """One plane offset: up to three pass scans over the block's stripe
    columns, cursor/distortion snapshots written at each pass end. The
    first coded plane (``off == 0``, peeled by the caller) runs only
    cleanup — its sigprop/magref trips are structurally absent, not
    masked."""
    sig_step, mag_step, cln_step = steps
    chi, pi, ref, cur, buf, counts, dh, dl = state
    valid = off < jnp.maximum(nbp - floor, 0)
    p = jnp.maximum(nbp - 1 - off, 0)

    def run_pass(step, t, chi, pi, ref, cur, buf, counts, dh, dl):
        carry = (chi, pi, ref, cur, buf, jnp.float32(0.0),
                 jnp.float32(0.0))
        carry = lax.fori_loop(
            0, COL_TRIPS, lambda tau, c: step(p, valid, c, tau), carry)
        chi, pi, ref, cur, buf, dsh, dsl = carry
        at = (off.astype(jnp.int32), jnp.int32(t))
        counts = lax.dynamic_update_slice(counts, cur.reshape(1, 1), at)
        dh = lax.dynamic_update_slice(dh, dsh.reshape(1, 1), at)
        dl = lax.dynamic_update_slice(dl, dsl.reshape(1, 1), at)
        return chi, pi, ref, cur, buf, counts, dh, dl

    st = (chi, pi, ref, cur, buf, counts, dh, dl)
    if not first:
        st = run_pass(sig_step, 0, *st)
        st = run_pass(mag_step, 1, *st)
    st = run_pass(cln_step, 2, *st)
    chi, pi, ref, cur, buf, counts, dh, dl = st
    # The coded-this-plane flags reset after every cleanup pass.
    pi = jnp.zeros_like(pi)
    return (chi, pi, ref, cur, buf, counts, dh, dl)


def _cxd_single(L, frac_bits, coeffs, nbp, floor, cls, h, w,
                tables=None, batch_emit=True):
    """The full per-block CX/D scan — shared verbatim between the
    vmapped jnp path and the Pallas kernel (which passes ``tables`` and
    ``batch_emit=False``). Returns (buf (max_syms,) uint8,
    counts/dh/dl (L, 3) indexed by plane *offset* from the block's MSB,
    cursor int32)."""
    idx = (jnp.abs(coeffs) >> frac_bits).astype(jnp.int32)
    # Bits below the floor are truncated away exactly as the packed
    # payload never ships them: byte-parity of the PCRD decisions
    # requires reproducing the floored magnitudes, not the
    # full-precision values.
    idx = (idx >> floor) << floor
    neg = (coeffs < 0).astype(jnp.int32)
    steps = _make_steps(L, idx, neg, nbp, floor, cls, h, w, tables,
                        batch_emit)
    state = _scan_plane(steps, nbp, floor, init_state(L),
                        jnp.int32(0), True)
    if L > 1:
        state = lax.fori_loop(
            1, L,
            lambda off, st: _scan_plane(steps, nbp, floor, st, off,
                                        False),
            state)
    _, _, _, cur, buf, counts, dh, dl = state
    return buf, counts, dh, dl, cur


def pack6(buf: jnp.ndarray) -> jnp.ndarray:
    """(N, max_syms) uint8 symbols -> (N, max_syms*3/4) uint8, four 6-bit
    symbols per little-endian 24-bit group."""
    n, m = buf.shape
    q = buf.reshape(n, m // 4, 4).astype(jnp.int32)
    word = q[..., 0] | (q[..., 1] << 6) | (q[..., 2] << 12) | (q[..., 3] << 18)
    out = jnp.stack([word & 0xFF, (word >> 8) & 0xFF, (word >> 16) & 0xFF],
                    axis=-1)
    return out.astype(jnp.uint8).reshape(n, m * 3 // 4)


def unpack6(packed: np.ndarray, n_syms: int) -> np.ndarray:
    """Host-side inverse of :func:`pack6` for one block's byte region."""
    groups = np.frombuffer(packed.tobytes(), dtype=np.uint8)
    groups = groups[:-(len(groups) % 3) or None].reshape(-1, 3).astype(
        np.int32)
    word = groups[:, 0] | (groups[:, 1] << 8) | (groups[:, 2] << 16)
    syms = np.stack([(word >> (6 * r)) & 63 for r in range(4)],
                    axis=1).reshape(-1)
    return syms[:n_syms].astype(np.uint8)


# Why the Pallas Tier-1 kernels (codec/pallas/) run in interpret mode
# only: compiled for a TPU v5e, Mosaic refuses them twice over.
MOSAIC_REFUSAL = (
    "the Pallas Tier-1 kernels are interpret-only: Mosaic refuses their "
    "(1, 6) SMEM meta block and the (1, msym), (1, cap) and (1, 1) "
    "blocks (the last two block dimensions must be divisible by 8 and "
    "128), and with a one-block grid it still refuses the shared "
    "_cxd_single body (NotImplementedError: Unimplemented primitive in "
    "Pallas TPU lowering: dynamic_slice)")


class PallasRefused(RuntimeError):
    """A compiled (non-interpret) Pallas Tier-1 program was asked for."""


def _use_pallas() -> bool:
    """Whether ``BUCKETEER_CXD_PALLAS`` asks for the Pallas kernels.
    Off unless set truthy; the fused jnp programs are the device
    Tier-1 on every backend."""
    return cfg_truthy(os.environ.get("BUCKETEER_CXD_PALLAS"))


def _check_pallas(interpret: bool) -> None:
    """Raise at program build for a compiled Pallas Tier-1 program:
    no backend lowers these kernels (:data:`MOSAIC_REFUSAL`)."""
    if not interpret:
        raise PallasRefused(f"BUCKETEER_CXD_PALLAS: {MOSAIC_REFUSAL}")


def _scan_impl(L: int, pallas: bool, interpret: bool):
    """The batched scan core as ``impl(frac, blocks, nbps, floors,
    cls, hs, ws)``. ``frac`` (the fixed-point shift) is a *runtime*
    scalar, not a compile key: it only ever feeds shift ops, and
    keeping it dynamic halves the fleet of ~20 s program compiles
    (lossless and lossy encodes share one variant per L)."""
    if pallas:
        _check_pallas(interpret)
        from .pallas.cxd_scan import cxd_pallas
        return partial(cxd_pallas, L, interpret=interpret)
    return jax.vmap(partial(_cxd_single, L),
                    in_axes=(None, 0, 0, 0, 0, 0, 0))


def _cxd_body(impl, blocks, nbps, floors, cls, hs, ws, frac):
    buf, counts, dh, dl, cur = impl(frac, blocks, nbps, floors, cls,
                                    hs, ws)
    packed = pack6(buf).reshape(-1, PACKED_ROW_BYTES)
    return packed, counts, dh, dl, cur


def cxd_program(L: int, pallas: bool | None = None,
                interpret: bool = False):
    """(traceable fn, device donate_argnums) for one CX/D program —
    the construction :func:`_compiled_cxd` jits, shared with the device
    audit (analysis/deviceaudit.py), which lowers both implementations
    on CPU (the Pallas kernel in interpret mode). ``pallas=None``
    defers to the runtime choice (:func:`_use_pallas`). ``L`` is the
    launch group's plane budget (the scan depth), not the chunk plane
    capacity; the fixed-point shift is the trailing runtime scalar.
    The donate spec is empty by verified fact: no output aval matches
    the (N, 64, 64) int32 block input (symbol rows are uint8, tables
    are per-pass), so XLA would drop the alias silently."""
    impl = _scan_impl(L, _use_pallas() if pallas is None else pallas,
                      interpret)
    return retrace.instrument("cxd", partial(_cxd_body, impl)), ()


@lru_cache(maxsize=64)
def _compiled_cxd(L: int):
    """One jitted CX/D program per plane budget, built outside the
    traced body (``BUCKETEER_CXD_PALLAS=1`` raises here)."""
    fn, donate = cxd_program(L)
    return jax.jit(fn, donate_argnums=donate_argnums_if_supported(*donate))


# --- host-side result assembly ------------------------------------------

@dataclass
class CxdStreams:
    """One chunk's CX/D payload, host-side: packed symbol rows plus the
    ordered pass tables the MQ replay walks."""
    payload: np.ndarray        # (R, 384) uint8 packed symbol rows
    row_offsets: np.ndarray    # (n,) int64 first payload row per block
    nbps: np.ndarray           # (n,) int32
    pass_offsets: np.ndarray   # (n+1,) int64 into the pass arrays
    pass_types: np.ndarray     # int32 0=sigprop 1=magref 2=cleanup
    pass_planes: np.ndarray    # int32
    pass_nsyms: np.ndarray     # int32 symbols in this pass
    pass_dists: np.ndarray     # float64 exact distortion reduction
    total_syms: int


def pass_tables(nbps: np.ndarray, floors: np.ndarray, counts: np.ndarray,
                dh: np.ndarray, dl: np.ndarray):
    """Per-block ordered pass lists from the device's cursor snapshots.

    ``counts[b, o, t]`` is the symbol cursor after pass (o, t) where
    ``o`` is the plane *offset* from the block's MSB (absolute plane
    ``p = nbp-1-o``); walking passes in coding order and differencing
    recovers per-pass symbol counts. Returns (pass_offsets (n+1,)
    int64, types, planes, nsyms int32 arrays, dists float64, totals
    (n,) int64).
    """
    n = len(nbps)
    types, planes, nsyms, dists = [], [], [], []
    offsets = np.zeros(n + 1, dtype=np.int64)
    totals = np.zeros(n, dtype=np.int64)
    dist = (dh.astype(np.float64) + dl.astype(np.float64)) / 4.0
    for b in range(n):
        prev = 0
        nbp, flo = int(nbps[b]), int(floors[b])
        for p in range(nbp - 1, flo - 1, -1):
            o = nbp - 1 - p
            for t in ((2,) if p == nbp - 1 else (0, 1, 2)):
                c = int(counts[b, o, t])
                types.append(t)
                planes.append(p)
                nsyms.append(c - prev)
                dists.append(dist[b, o, t])
                prev = c
        totals[b] = prev
        offsets[b + 1] = len(types)
    return (offsets, np.asarray(types, np.int32),
            np.asarray(planes, np.int32), np.asarray(nsyms, np.int32),
            np.asarray(dists, np.float64), totals)


def replay_block(syms: np.ndarray, nbp: int, n_passes: int,
                 pass_types, pass_planes, pass_nsyms, pass_dists):
    """Pure-Python MQ replay of one block's symbol stream — the
    no-native fallback and the test reference. Returns t1.CodedBlock."""
    from . import t1

    mq = MQEncoder()
    passes = []
    pos = 0
    for j in range(n_passes):
        for s in syms[pos:pos + int(pass_nsyms[j])]:
            mq.encode(int(s) >> 5, int(s) & 31)
        pos += int(pass_nsyms[j])
        passes.append(t1.PassInfo(int(pass_types[j]), int(pass_planes[j]),
                                  mq.truncation_length(),
                                  float(pass_dists[j])))
    data = mq.flush() if n_passes else b""
    for info in passes:
        info.cum_length = min(info.cum_length, len(data))
    return t1.CodedBlock(data, nbp if n_passes else 0, passes)


class RecordingMQEncoder(MQEncoder):
    """MQEncoder that also records the (context, decision) sequence and
    the symbol count at every truncation point — the ground truth the
    device CX/D streams are tested against (tests/test_cxd.py)."""

    def __init__(self) -> None:
        super().__init__()
        self.symbols: list = []
        self.boundaries: list = []

    def encode(self, bit: int, ctx: int) -> None:
        self.symbols.append(ctx | (bit << 5))
        super().encode(bit, ctx)

    def truncation_length(self) -> int:
        self.boundaries.append(len(self.symbols))
        return super().truncation_length()


def reference_cxd(mags: np.ndarray, signs: np.ndarray, band: str,
                  floor: int = 0):
    """Reference CX/D stream via codec/t1.py with a recording coder.
    Returns (CodedBlock, symbols uint8 array, pass boundary list)."""
    from . import t1

    rec = RecordingMQEncoder()
    blk = t1.encode_block(mags, signs, band, floor=floor, mq=rec)
    return blk, np.asarray(rec.symbols, dtype=np.uint8), rec.boundaries


# --- Mb-clamped launch groups -------------------------------------------

def _eff_groups(nbps: np.ndarray, floors: np.ndarray):
    """Partition a chunk's blocks into LAUNCH_PLANE_BUCKETS of their
    realized scan depth ``eff = max(nbp - floor, 0)`` — the Mb clamp.
    Dead
    blocks (``eff == 0``: all-zero, or floored away entirely) appear
    in no group and cost zero trips. Groups smaller than
    GROUP_MIN_BLOCKS merge into the next larger bucket (their extra
    plane offsets are masked) so launch count stays bounded. Returns
    ([(L, original-index int64 array)], eff)."""
    eff = np.maximum(nbps.astype(np.int64) - floors.astype(np.int64), 0)
    by_l: dict = {}
    for i in np.nonzero(eff > 0)[0]:
        by_l.setdefault(_launch_bucket(int(eff[i])), []).append(int(i))
    groups = []
    pending: list = []
    for li, l_val in enumerate(sorted(by_l)):
        idxs = pending + by_l[l_val]
        if len(idxs) < GROUP_MIN_BLOCKS and li < len(by_l) - 1:
            pending = idxs
            continue
        groups.append((l_val, np.asarray(sorted(idxs), np.int64)))
        pending = []
    return groups, eff


GROUP_BATCH_FLOOR = 8    # smallest launch batch (lanes); see _group_meta


def _group_meta(idxs: np.ndarray, nbps, floors, bandnames, hs, ws):
    """Per-launch metadata for one group, padded to a pow-2 batch with
    a floor of GROUP_BATCH_FLOOR lanes (the padding tail points at
    block 0 with dead meta — nbp 0, floor 1 — which emits nothing).
    The floor exists for the compile fleet, not the device: every
    distinct (L, N) pair is its own ~20 s XLA compile, and tiny
    chunks would otherwise mint N ∈ {1, 2, 4} variants whose dead-lane
    cost is microseconds. The padding invariant is shared by the
    replay-mode (:func:`run_cxd`) and device-MQ
    (:func:`run_device_mq`) paths — it must not diverge between
    them."""
    g = len(idxs)
    nb = _pow2ceil(max(g, GROUP_BATCH_FLOOR))
    pad = nb - g
    sel = np.concatenate([idxs, np.zeros(pad, np.int64)])
    nbps_d = nbps[sel].astype(np.int32)
    floors_d = floors[sel].astype(np.int32)
    cls = np.asarray([BAND_CLS[bandnames[i]] for i in idxs]
                     + [0] * pad, np.int32)
    hs_d = hs[sel].astype(np.int32)
    ws_d = ws[sel].astype(np.int32)
    if pad:
        nbps_d[g:] = 0
        floors_d[g:] = 1
        hs_d[g:] = CBLK
        ws_d[g:] = CBLK
    return sel, nbps_d, floors_d, cls, hs_d, ws_d


def _launch_args(blocks_dev, sel, nbps_d, floors_d, cls, hs_d, ws_d):
    return (blocks_dev[jnp.asarray(sel)], jnp.asarray(nbps_d),
            jnp.asarray(floors_d), jnp.asarray(cls),
            jnp.asarray(hs_d), jnp.asarray(ws_d))


def _group_launches(blocks_dev, nbps, floors, bandnames, hs, ws,
                    frac_bits):
    """Iterate one chunk's Mb-clamped launch groups: yields
    (L, idxs, g, program args incl. the runtime frac scalar), with the
    workload-shape histogram recorded per *launch* — lanes really
    padded (``cxd.blocks``) and plane offsets really masked
    (``cxd.planes``). This is the single place the group
    padding/metadata invariant lives, so the replay
    (:func:`run_cxd`) and device-MQ (:func:`run_device_mq`) paths
    cannot diverge."""
    groups, eff = _eff_groups(nbps, floors)
    for L, idxs in groups:
        sel, nbps_g, floors_g, cls_g, hs_g, ws_g = _group_meta(
            idxs, nbps, floors, bandnames, hs, ws)
        g = len(idxs)
        graftcost.record_bucket("cxd.blocks", g, len(sel))
        graftcost.record_bucket("cxd.planes", int(eff[idxs].max()), L)
        args = _launch_args(blocks_dev, sel, nbps_g, floors_g, cls_g,
                            hs_g, ws_g) + (jnp.int32(frac_bits),)
        yield L, idxs, g, args


def _check_sym_overflow(max_cursor: int, L: int) -> None:
    if max_cursor > max_syms(L):
        raise ValueError(
            f"CX/D stream overflow: {max_cursor} symbols exceed the "
            f"static capacity {max_syms(L)} (L={L})")


_EMPTY_I32 = np.zeros(0, np.int32)
_EMPTY_F64 = np.zeros(0, np.float64)


def run_cxd(blocks_dev, nbps: np.ndarray, floors: np.ndarray,
            bandnames: list, hs: np.ndarray, ws: np.ndarray,
            P: int, frac_bits: int) -> CxdStreams:
    """Run the device CX/D scan for one chunk and fetch its streams.

    ``blocks_dev``: (N, 64, 64) int32 device array (N >= n real blocks;
    the tail is batch padding). The chunk's blocks launch in Mb-clamped
    groups (:func:`_eff_groups`): each group scans only its pow-2
    bucket of realized plane depths, and only the packed symbol rows
    each live block actually filled travel device->host (row-granular
    gather, like frontend.fetch_payload). ``P`` caps nothing anymore —
    it is kept for the callers' signature and as a sanity ceiling."""
    n = len(nbps)
    empty_rows = np.zeros((0, PACKED_ROW_BYTES), np.uint8)
    per_rows = [empty_rows] * n
    per_types = [_EMPTY_I32] * n
    per_planes = [_EMPTY_I32] * n
    per_nsyms = [_EMPTY_I32] * n
    per_dists = [_EMPTY_F64] * n
    total = 0
    for L, idxs, g, args in _group_launches(blocks_dev, nbps, floors,
                                            bandnames, hs, ws,
                                            frac_bits):
        packed, counts, dh, dl, cur = _compiled_cxd(L)(*args)
        counts_h, dh_h, dl_h = (np.asarray(jax.device_get(a))[:g]
                                for a in (counts, dh, dl))
        offs, types, planes, nsyms, dists, totals_g = pass_tables(
            nbps[idxs], floors[idxs], counts_h, dh_h, dl_h)
        if totals_g.size:
            _check_sym_overflow(int(totals_g.max()), L)
        payload_g, row_offs_g = _fetch_block_rows(
            packed, -(-totals_g // SYMS_PER_ROW), rows_per_block(L),
            PACKED_ROW_BYTES)
        for k, i in enumerate(idxs):
            per_rows[i] = payload_g[int(row_offs_g[k]):
                                    int(row_offs_g[k + 1])]
            sl = slice(int(offs[k]), int(offs[k + 1]))
            per_types[i] = types[sl]
            per_planes[i] = planes[sl]
            per_nsyms[i] = nsyms[sl]
            per_dists[i] = dists[sl]
        total += int(totals_g.sum())

    row_offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum([len(r) for r in per_rows], out=row_offsets[1:])
    pass_offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum([len(t) for t in per_types], out=pass_offsets[1:])
    payload = (np.concatenate(per_rows) if n else empty_rows)
    return CxdStreams(payload, row_offsets[:-1], nbps.astype(np.int32),
                      pass_offsets,
                      np.concatenate(per_types) if n else _EMPTY_I32,
                      np.concatenate(per_planes) if n else _EMPTY_I32,
                      np.concatenate(per_nsyms) if n else _EMPTY_I32,
                      np.concatenate(per_dists) if n else _EMPTY_F64,
                      total)


def _fetch_block_rows(rows_dev, rows_needed: np.ndarray, rpb: int,
                      row_bytes: int):
    """Row-granular device->host fetch shared by the symbol-stream and
    byte-segment payloads: block b owns rows [b*rpb, (b+1)*rpb) of the
    device array and ships only its first ``rows_needed[b]``. Returns
    (payload (R, row_bytes) uint8, row_offsets (n+1,) int64)."""
    from . import frontend

    n = len(rows_needed)
    row_offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(rows_needed, out=row_offsets[1:])
    src = np.empty(int(row_offsets[-1]), dtype=np.int64)
    for b in np.nonzero(rows_needed)[0]:
        o = row_offsets[b]
        src[o:row_offsets[b + 1]] = (b * rpb
                                     + np.arange(rows_needed[b]))
    return frontend.gather_rows(rows_dev, src, row_bytes), row_offsets


# --- the device MQ coder (run_device_mq) ---------------------------------
#
# A byte-emitting scan over the CX/D symbol buffer replicating the host
# MQEncoder register for register: A (16-bit interval), C (32-bit code,
# uint32 with the host's & 0xFFFFFFFF masks as native wraparound), CT
# (shift countdown), the 47-entry Qe state table, per-context
# index/MPS, the spec's byte-stuffing byteout (Annex C.2.5 incl. the
# carry that increments the previous byte) and the two-byteout flush
# with the software-convention trailing-0xFF drop. Per-pass truncation
# points are captured in-scan: whenever the symbol cursor crosses a
# pass boundary (the CX/D scan's ``counts`` snapshots), the byte count
# at that moment is recorded — exactly what ``MQEncoder.n_bytes()``
# returns when ``truncation_length`` is called at the end of a pass.
#
# Structure (this PR): MQ_UNROLL symbols per scan trip, renorm as an
# arithmetic shift count with at most three masked byteouts, and the
# last emitted byte held in a ``pending`` register so byteout is one
# masked store with no buffer read. The batched form runs whole chunks
# through one loop (the fused program); the scalar form is the Pallas
# kernels' and the oracle tests' per-block path — both share the step
# logic through the ``ops`` seam below.

MQ_ROW_BYTES = 512       # byte-segment fetch granularity (gather_rows)
MQ_UNROLL = 8            # symbols per MQ scan trip

_QE_ARR = np.asarray(QE_TABLE, dtype=np.int32)     # (47, 4)


def mq_capacity(n_steps: int) -> int:
    """Static byte capacity for ``n_steps`` symbols, rounded to fetch
    rows. Each MQ decision is one binary symbol; the coder's sustained
    worst case is well under 2 bits/decision (a 15-shift emission needs
    an LPS at a tiny-Qe state, reachable only through long runs of
    sub-bit MPS coding), so 4 bits/symbol plus transient slack is a
    hard ceiling in practice — and :func:`run_device_mq` verifies the
    realized cursor against this capacity and fails loudly rather than
    ship a silently truncated stream."""
    cap = n_steps // 2 + 64
    return -(-cap // MQ_ROW_BYTES) * MQ_ROW_BYTES


def _mq_ops(batched: bool):
    """The shape seam between the batched MQ path (whole chunks, (n,)
    registers, used by the fused program and :func:`_mq_run`) and the
    scalar path (one block, used by the Pallas kernels). Everything
    else in the step is shape-polymorphic jnp."""
    if not batched:
        return SimpleNamespace(
            write=lambda buf, cond, pos, val, oob:
                buf.at[jnp.where(cond, pos, oob)].set(
                    val.astype(jnp.uint8), mode="drop"),
            ctx_get=lambda tab, ctx: tab[ctx],
            ctx_set=lambda tab, ctx, v: tab.at[ctx].set(v),
            read_chunk=lambda symbuf, s0, k:
                lax.dynamic_slice(symbuf, (s0,), (k,)),
            chunk_col=lambda chunk, k: chunk[k],
            snap=lambda snaps, counts, live, s, cur:
                jnp.where(live & (counts == s + 1), cur - 1, snaps),
        )

    def _bwrite(buf, cond, pos, val, oob):
        n = buf.shape[0]
        return buf.at[jnp.arange(n), jnp.where(cond, pos, oob)].set(
            val.astype(jnp.uint8), mode="drop")

    def _bctx_get(tab, ctx):
        return tab[jnp.arange(tab.shape[0]), ctx]

    def _bctx_set(tab, ctx, v):
        return tab.at[jnp.arange(tab.shape[0]), ctx].set(v)

    return SimpleNamespace(
        write=_bwrite,
        ctx_get=_bctx_get,
        ctx_set=_bctx_set,
        read_chunk=lambda symbuf, s0, k:
            lax.dynamic_slice(symbuf, (0, s0), (symbuf.shape[0], k)),
        chunk_col=lambda chunk, k: chunk[:, k],
        snap=lambda snaps, counts, live, s, cur:
            jnp.where(live[:, None, None] & (counts == s + 1),
                      (cur - 1)[:, None, None], snaps),
    )


def _mq_state(ops, shape, L, cap):
    """Carry: (a, c, ct, cursor, pending byte at cursor-1, byte buffer,
    per-context Qe indices, per-context MPS, per-pass byte snapshots).
    ``pending`` starts as the software convention's dummy pre-byte
    (MQEncoder.buf[0]) and is finalized into the buffer at the next
    byteout (or at flush). Context init by scalar updates, not an
    embedded array — Pallas kernels cannot capture array constants."""
    full = lambda v, dt=jnp.int32: jnp.full(shape, v, dt)  # noqa: E731
    idxs = jnp.zeros(shape + (19,), jnp.int32)
    idxs = idxs.at[..., 0].set(4).at[..., CTX_RL].set(3) \
        .at[..., CTX_UNIFORM].set(46)
    return (full(0x8000), full(0, jnp.uint32), full(12), full(1),
            full(0), jnp.zeros(shape + (cap,), jnp.uint8), idxs,
            jnp.zeros(shape + (19,), jnp.int32),
            jnp.zeros(shape + (L, 3), jnp.int32))


def _mq_byteout(ops, cond, c, ct, pending, buf, cur, cap):
    """Annex C.2.5 BYTEOUT, masked by ``cond``: finalize the pending
    byte at ``cur - 1`` (applying the carry that increments it when
    C overflowed), make the next byte of C pending (stuffed after
    0xFF), update C/CT. One masked store, no buffer read."""
    is_ff = pending == 0xFF
    carry = jnp.logical_not(is_ff) & (c >= jnp.uint32(0x8000000))
    newlast = jnp.where(carry, pending + 1, pending)
    stuff = is_ff | (carry & (newlast == 0xFF))
    c2 = jnp.where(carry & (newlast == 0xFF),
                   c & jnp.uint32(0x7FFFFFF), c)
    out_b = (jnp.where(stuff, c2 >> jnp.uint32(20),
                       c2 >> jnp.uint32(19)) & jnp.uint32(0xFF)
             ).astype(jnp.int32)
    buf = ops.write(buf, cond, cur - 1, newlast, cap)
    pending = jnp.where(cond, out_b, pending)
    c = jnp.where(cond, jnp.where(stuff, c2 & jnp.uint32(0xFFFFF),
                                  c2 & jnp.uint32(0x7FFFF)), c)
    ct = jnp.where(cond, jnp.where(stuff, 7, 8), ct)
    return c, ct, pending, buf, cur + cond.astype(jnp.int32)


def _mq_renorm(ops, cond, a, c, ct, pending, buf, cur, cap):
    """Annex C.2.4 RENORME without the per-shift loop: the shift count
    k (<= 15) comes from 15 comparisons, C advances in up to three
    chunks split at the CT expiries, and each expiry is one masked
    byteout. Three byteouts are provably enough: the first costs
    k1 = CT <= 12 shifts, each later one reloads CT to 7 or 8, and
    k <= 15 leaves at most 7 shifts after the second."""
    k = jnp.zeros_like(ct)
    for i in range(1, 16):
        k = k + (a < (1 << (16 - i))).astype(jnp.int32)
    k = jnp.where(cond, k, 0)
    a = jnp.where(cond, (a << k) & 0xFFFF, a)
    rem = k
    b_prev = cond
    for _ in range(3):
        kk = jnp.minimum(rem, ct)
        c = c << kk.astype(jnp.uint32)
        ct = ct - kk
        b_here = b_prev & (ct == 0)
        c, ct, pending, buf, cur = _mq_byteout(ops, b_here, c, ct,
                                               pending, buf, cur, cap)
        rem = rem - kk
        b_prev = b_here
    return a, c, ct, pending, buf, cur


def _mq_sym_step(ops, qe_tab, cap, counts, totals, s, sym, carry):
    """One MQ symbol (Annex C.2.2/C.2.3 interval update with
    conditional exchange collapsed to two selects, then renorm), masked
    dead once the block's realized cursor is passed. ``s`` is the
    global symbol index — shared across the batch, so pass-boundary
    snapshots (``counts == s + 1``) land exactly where the host's
    ``truncation_length`` calls would."""
    a, c, ct, cur, pending, buf, idxs, mpss, snaps = carry
    live = s < totals
    sym = sym.astype(jnp.int32)
    d = sym >> 5
    ctx = sym & 31
    idx = ops.ctx_get(idxs, ctx)
    qe = qe_tab[idx, 0]
    mps = ops.ctx_get(mpss, ctx)
    is_mps = d == mps
    a1 = a - qe
    renorm_mps = (a1 & 0x8000) == 0
    lt = a1 < qe
    new_a = jnp.where(is_mps == lt, qe, a1)
    add_c = jnp.where(is_mps != lt, qe, 0)
    new_idx = jnp.where(is_mps,
                        jnp.where(renorm_mps, qe_tab[idx, 1], idx),
                        qe_tab[idx, 2])
    new_mps = jnp.where(jnp.logical_not(is_mps)
                        & (qe_tab[idx, 3] == 1), 1 - mps, mps)
    idxs = ops.ctx_set(idxs, ctx, jnp.where(live, new_idx, idx))
    mpss = ops.ctx_set(mpss, ctx, jnp.where(live, new_mps, mps))
    a = jnp.where(live, new_a, a)
    c = c + jnp.where(live, add_c, 0).astype(jnp.uint32)
    need_rn = live & jnp.where(is_mps, renorm_mps, True)
    a, c, ct, pending, buf, cur = _mq_renorm(ops, need_rn, a, c, ct,
                                             pending, buf, cur, cap)
    snaps = ops.snap(snaps, counts, live, s, cur)
    return (a, c, ct, cur, pending, buf, idxs, mpss, snaps)


def _mq_chunk_step(ops, qe_tab, cap, symbuf, counts, totals, s0, carry):
    """One scan trip: MQ_UNROLL consecutive symbols, read with a single
    contiguous slice."""
    chunk = ops.read_chunk(symbuf, s0, MQ_UNROLL)
    for k in range(MQ_UNROLL):
        carry = _mq_sym_step(ops, qe_tab, cap, counts, totals, s0 + k,
                             ops.chunk_col(chunk, k), carry)
    return carry


def _mq_flush(ops, carry, do_flush, cap):
    """Annex C.2.9 FLUSH (masked by ``do_flush`` — blocks with no
    coding passes ship no bytes, mirroring ``replay_block``'s
    ``mq.flush() if n_passes else b""``), plus the software
    convention's trailing-0xFF drop. Returns (buf, snaps, data_len,
    cursor)."""
    a, c, ct, cur, pending, buf, idxs, mpss, snaps = carry
    tempc = c + a.astype(jnp.uint32)
    c = c | jnp.uint32(0xFFFF)
    c = jnp.where(c >= tempc, c - jnp.uint32(0x8000), c)
    c = c << ct.astype(jnp.uint32)
    c, ct, pending, buf, cur = _mq_byteout(ops, do_flush, c, ct,
                                           pending, buf, cur, cap)
    c = c << ct.astype(jnp.uint32)
    c, ct, pending, buf, cur = _mq_byteout(ops, do_flush, c, ct,
                                           pending, buf, cur, cap)
    # Finalize the outstanding byte; the trailing-0xFF drop reads it
    # from the register, not the buffer.
    buf = ops.write(buf, do_flush, cur - 1, pending, cap)
    nbytes = cur - 1
    dlen = nbytes - (pending == 0xFF).astype(jnp.int32)
    dlen = jnp.where(do_flush, dlen, 0)
    return buf, snaps, dlen, cur


def _mq_run(L, n_steps, cap, symbuf, counts, totals, flags):
    """Batched MQ scan over a fixed symbol budget (pow-2 bucket or the
    oracle tests' stream length; must be a multiple of MQ_UNROLL).
    (n, S) uint8 symbols + (n, L, 3) pass cursors + (n,) totals and
    flush flags -> (bytebuf (n, cap) uint8, snaps (n, L, 3) int32,
    dlen (n,) int32, cursors (n,) int32)."""
    if n_steps % MQ_UNROLL:
        raise ValueError(f"n_steps {n_steps} not a multiple of "
                         f"MQ_UNROLL {MQ_UNROLL}")
    ops = _mq_ops(batched=True)
    qe_tab = jnp.asarray(_QE_ARR)
    n = symbuf.shape[0]
    carry = _mq_state(ops, (n,), L, cap)
    carry = lax.fori_loop(
        0, n_steps // MQ_UNROLL,
        lambda t, cr: _mq_chunk_step(ops, qe_tab, cap, symbuf, counts,
                                     totals, t * MQ_UNROLL, cr),
        carry)
    return _mq_flush(ops, carry, flags != 0, cap)


def _mq_drive_while(ops, qe_tab, cap, symbuf, counts, totals, limit,
                    carry):
    """Realized-cursor MQ loop skeleton shared by the batched fused
    body and the fused Pallas kernel (scalar ops): MQ_UNROLL-symbol
    trips until the cursor ``limit`` — symbol capacity is a multiple
    of MQ_UNROLL, so the last chunk slice stays in bounds."""
    def cond(st):
        return st[0] < limit

    def body(st):
        s0, cr = st[0], st[1:]
        cr = _mq_chunk_step(ops, qe_tab, cap, symbuf, counts, totals,
                            s0, cr)
        return (s0 + MQ_UNROLL,) + cr

    st = lax.while_loop(cond, body, (jnp.int32(0),) + carry)
    return st[1:]


def _mq_run_while(L, cap, symbuf, counts, totals, flags):
    """Batched MQ scan whose trip count is the chunk's *realized*
    maximum cursor — the fused program's form: no host round-trip to
    pick a bucket, trips stop at ``max(totals)``."""
    ops = _mq_ops(batched=True)
    qe_tab = jnp.asarray(_QE_ARR)
    n = symbuf.shape[0]
    carry = _mq_drive_while(ops, qe_tab, cap, symbuf, counts, totals,
                            jnp.max(totals), _mq_state(ops, (n,), L, cap))
    return _mq_flush(ops, carry, flags != 0, cap)


def _mq_single(L, n_steps, cap, symbuf, counts, total, flush_flag):
    """Per-block wrapper over the batched scan — the oracle tests' and
    the TPU parity tests' entry point."""
    buf, snaps, dlen, cur = _mq_run(
        L, n_steps, cap, symbuf[None], counts[None].astype(jnp.int32),
        total[None] if hasattr(total, "shape") else
        jnp.asarray([total], jnp.int32),
        jnp.asarray([flush_flag], jnp.int32)
        if not hasattr(flush_flag, "shape") else flush_flag[None])
    return buf[0], snaps[0], dlen[0], cur[0]


# --- the fused CX/D -> MQ program ---------------------------------------

def _fused_body(L, impl_scan, blocks, nbps, floors, cls, hs, ws, frac):
    """CX/D scan chained straight into the MQ coder inside one traced
    program: the (N, max_syms) symbol buffer is an internal value —
    never a program output, never reconsumed from HBM (the
    perf-hbm-roundtrip the two-program chain used to carry). The MQ
    trip count is the realized maximum cursor, not a capacity."""
    buf, counts, dh, dl, cur = impl_scan(frac, blocks, nbps, floors,
                                         cls, hs, ws)
    cap = mq_capacity(max_syms(L))
    flags = (nbps > floors).astype(jnp.int32)
    rows, snaps, dlen, curb = _mq_run_while(L, cap, buf, counts, cur,
                                            flags)
    return (rows.reshape(-1, MQ_ROW_BYTES), snaps, dlen, dh, dl, cur,
            curb)


def fused_program(L: int, pallas: bool | None = None,
                  interpret: bool = False):
    """(traceable fn, device donate_argnums) for the fused device
    Tier-1 program — CX/D context modeling and the MQ coder in one
    launch, the construction :func:`_compiled_fused` jits, shared with
    the device audit (registry entries ``cxdmq.fused`` /
    ``cxdmq.fused.pallas``). Inputs match :func:`cxd_program`; outputs
    are byte-segment rows, per-pass byte snapshots (plane-offset
    indexed), data lengths, the distortion pairs, symbol cursors and
    byte cursors. The donate spec is empty by verified fact: no output
    aval matches the int32 block input."""
    if _use_pallas() if pallas is None else pallas:
        _check_pallas(interpret)
        from .pallas.fused_t1 import fused_pallas
        impl = partial(fused_pallas, L, interpret=interpret)

        def fn(blocks, nbps, floors, cls, hs, ws, frac):
            return impl(frac, blocks, nbps, floors, cls, hs, ws)
    else:
        fn = partial(_fused_body, L, _scan_impl(L, False, False))
    return retrace.instrument("cxdmq", fn), ()


@lru_cache(maxsize=64)
def _compiled_fused(L: int):
    fn, donate = fused_program(L)
    return jax.jit(fn, donate_argnums=donate_argnums_if_supported(*donate))


@dataclass
class MqDeviceResult:
    """One chunk's device-MQ outcome: finished code-blocks plus the
    symbols coded and the bytes they produced."""
    blocks: list               # [t1.CodedBlock]
    total_syms: int
    total_bytes: int


def assemble_mq_blocks(nbps: np.ndarray, floors: np.ndarray,
                       snaps: np.ndarray, dlens: np.ndarray,
                       dists: np.ndarray, payload: np.ndarray,
                       row_offsets: np.ndarray) -> list:
    """Host assembly of device-MQ outputs into ``t1.CodedBlock``s — the
    whole host share of Tier-1 in device-MQ mode (no MQ replay, no
    context modeling).

    ``snaps``: (n, L, 3) per-pass byte counts indexed by plane offset
    from each block's MSB; ``dlens``: (n,) final data lengths;
    ``dists``: (n, L, 3) float64 exact distortions; ``payload``:
    (R, MQ_ROW_BYTES) fetched byte rows, each block's segment starting
    with the dummy pre-byte; ``row_offsets``: (n+1,) first payload row
    per block."""
    from . import t1
    from .rate import truncation_lengths

    out = []
    for b in range(len(nbps)):
        nbp, flo = int(nbps[b]), int(floors[b])
        dlen = int(dlens[b])
        if nbp <= flo:
            out.append(t1.CodedBlock(b"", 0))
            continue
        raw = payload[int(row_offsets[b]):int(row_offsets[b + 1])]
        data = raw.reshape(-1)[1:1 + dlen].tobytes()
        # One vectorized truncation-point map per block; the pass walk
        # below only indexes it (this loop is the host's entire Tier-1
        # share — keep numpy dispatch out of the per-pass path).
        cums = truncation_lengths(snaps[b], dlen)
        passes = []
        for p in range(nbp - 1, flo - 1, -1):
            o = nbp - 1 - p
            for t in ((2,) if p == nbp - 1 else (0, 1, 2)):
                passes.append(t1.PassInfo(t, p, int(cums[o, t]),
                                          float(dists[b, o, t])))
        out.append(t1.CodedBlock(data, nbp, passes))
    return out


def run_device_mq(blocks_dev, nbps: np.ndarray, floors: np.ndarray,
                  bandnames: list, hs: np.ndarray, ws: np.ndarray,
                  P: int, frac_bits: int) -> MqDeviceResult:
    """Tier-1 for one chunk entirely on device: the fused CX/D + MQ
    program per Mb-clamped launch group (the symbol buffer stays
    on-chip), then a row-granular fetch of the finished byte segments +
    per-pass truncation snapshots. Output blocks are byte-identical to
    ``t1_batch.encode_cxd`` over ``run_cxd`` streams (and therefore to
    the legacy packed path)."""
    from . import t1

    n = len(nbps)
    out = [t1.CodedBlock(b"", 0) for _ in range(n)]
    tot_syms = tot_bytes = 0
    for L, idxs, g, args in _group_launches(blocks_dev, nbps, floors,
                                            bandnames, hs, ws,
                                            frac_bits):
        cap = mq_capacity(max_syms(L))

        rows, snaps, dlen, dh, dl, cur, curb = _compiled_fused(L)(*args)
        snaps_h, dlen_h, dh_h, dl_h, cur_h, curb_h = (
            np.asarray(jax.device_get(x))[:g]
            for x in (snaps, dlen, dh, dl, cur, curb))

        if g:
            _check_sym_overflow(int(cur_h.max()), L)
        if g and int(curb_h.max()) > cap:
            raise ValueError(
                f"MQ byte-segment overflow: {int(curb_h.max())} bytes "
                f"exceed the static capacity {cap} — the coded stream "
                "expanded past the 4-bit/symbol budget")
        dist = (dh_h.astype(np.float64) + dl_h.astype(np.float64)) / 4.0

        # Row-granular byte fetch: only the rows each live block filled
        # (the block's segment includes the leading dummy pre-byte).
        payload, row_offs = _fetch_block_rows(
            rows, -(-(dlen_h + 1) // MQ_ROW_BYTES) * (dlen_h > 0),
            cap // MQ_ROW_BYTES, MQ_ROW_BYTES)

        blocks_g = assemble_mq_blocks(nbps[idxs], floors[idxs], snaps_h,
                                      dlen_h, dist, payload, row_offs)
        for k, i in enumerate(idxs):
            out[int(i)] = blocks_g[k]
        tot_syms += int(cur_h.sum())
        tot_bytes += int(dlen_h.sum())
    return MqDeviceResult(out, tot_syms, tot_bytes)
