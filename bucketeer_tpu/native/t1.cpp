// EBCOT Tier-1 native coder: MQ arithmetic coder + 3-pass bit-plane
// context modeling (JPEG 2000 Part 1, Annex C/D).
//
// This is the production entropy-coding path of the framework — the role
// the reference delegates to the proprietary Kakadu binary (reference:
// converters/AbstractConverter.java:29-39, KakaduConverter.java:38-44).
// It must stay bit-exact with the Python reference implementation in
// bucketeer_tpu/codec/{mq,t1}.py (enforced by tests/test_native_t1.py).
//
// Code-blocks are embarrassingly parallel; t1_encode_blocks fans a batch
// of blocks out over a std::thread pool (the host-side analog of the
// reference's Lambda fan-out, sized like its uploader pool — cores-1,
// reference: verticles/MainVerticle.java:64-77).
//
// The read path's decoder lives here too: t1_decode_blocks is the MQ
// decoder and context modelling of codec/{mq.py,decode/t1_dec.py}, one
// tile's blocks per call on the same pool, bit-exact with the Python
// decoder (enforced by tests/test_native_t1_decode.py).
//
// Build: make -C bucketeer_tpu/native  (g++ -O3, no external deps).

#include <algorithm>
#include <atomic>
#include <cstdint>
#include <cstring>
#include <thread>
#include <vector>

namespace {

// ---- MQ encoder (T.800 Annex C.2; mirrors codec/mq.py exactly) ----

struct QeRow { uint16_t qe; uint8_t nmps, nlps, sw; };

static const QeRow QE[47] = {
    {0x5601, 1, 1, 1},   {0x3401, 2, 6, 0},   {0x1801, 3, 9, 0},
    {0x0AC1, 4, 12, 0},  {0x0521, 5, 29, 0},  {0x0221, 38, 33, 0},
    {0x5601, 7, 6, 1},   {0x5401, 8, 14, 0},  {0x4801, 9, 14, 0},
    {0x3801, 10, 14, 0}, {0x3001, 11, 17, 0}, {0x2401, 12, 18, 0},
    {0x1C01, 13, 20, 0}, {0x1601, 29, 21, 0}, {0x5601, 15, 14, 1},
    {0x5401, 16, 14, 0}, {0x5101, 17, 15, 0}, {0x4801, 18, 16, 0},
    {0x3801, 19, 17, 0}, {0x3401, 20, 18, 0}, {0x3001, 21, 19, 0},
    {0x2801, 22, 19, 0}, {0x2401, 23, 20, 0}, {0x2201, 24, 21, 0},
    {0x1C01, 25, 22, 0}, {0x1801, 26, 23, 0}, {0x1601, 27, 24, 0},
    {0x1401, 28, 25, 0}, {0x1201, 29, 26, 0}, {0x1101, 30, 27, 0},
    {0x0AC1, 31, 28, 0}, {0x09C1, 32, 29, 0}, {0x08A1, 33, 30, 0},
    {0x0521, 34, 31, 0}, {0x0441, 35, 32, 0}, {0x02A1, 36, 33, 0},
    {0x0221, 37, 34, 0}, {0x0141, 38, 35, 0}, {0x0111, 39, 36, 0},
    {0x0085, 40, 37, 0}, {0x0049, 41, 38, 0}, {0x0025, 42, 39, 0},
    {0x0015, 43, 40, 0}, {0x0009, 44, 41, 0}, {0x0005, 45, 42, 0},
    {0x0001, 45, 43, 0}, {0x5601, 46, 46, 0},
};

constexpr int N_CTX = 19;
constexpr int CTX_RL = 17;
constexpr int CTX_UNIFORM = 18;

struct MQEnc {
    uint32_t a = 0x8000, c = 0;
    int ct = 12;
    std::vector<uint8_t> buf;
    uint8_t idx[N_CTX];
    uint8_t mps[N_CTX];

    MQEnc() {
        buf.reserve(4096);
        buf.push_back(0);  // dummy pre-byte
        std::memset(idx, 0, sizeof(idx));
        std::memset(mps, 0, sizeof(mps));
        idx[0] = 4;
        idx[CTX_RL] = 3;
        idx[CTX_UNIFORM] = 46;
    }

    void byteout() {
        if (buf.back() == 0xFF) {
            buf.push_back((c >> 20) & 0xFF);
            c &= 0xFFFFF;
            ct = 7;
        } else if (c < 0x8000000u) {
            buf.push_back((c >> 19) & 0xFF);
            c &= 0x7FFFF;
            ct = 8;
        } else {
            buf.back() += 1;
            if (buf.back() == 0xFF) {
                c &= 0x7FFFFFF;
                buf.push_back((c >> 20) & 0xFF);
                c &= 0xFFFFF;
                ct = 7;
            } else {
                buf.push_back((c >> 19) & 0xFF);
                c &= 0x7FFFF;
                ct = 8;
            }
        }
    }

    void renorm() {
        do {
            a = (a << 1) & 0xFFFF;
            c = c << 1;
            if (--ct == 0) byteout();
        } while (!(a & 0x8000));
    }

    void encode(int bit, int ctx) {
        const QeRow& row = QE[idx[ctx]];
        uint32_t qe = row.qe;
        if (bit == mps[ctx]) {
            a -= qe;
            if (!(a & 0x8000)) {
                if (a < qe) a = qe; else c += qe;
                idx[ctx] = row.nmps;
                renorm();
            } else {
                c += qe;
            }
        } else {
            a -= qe;
            if (a < qe) c += qe; else a = qe;
            if (row.sw) mps[ctx] ^= 1;
            idx[ctx] = row.nlps;
            renorm();
        }
    }

    int64_t trunc_length() const {
        return (int64_t)buf.size() - 1 + 4;
    }

    void flush() {
        uint32_t tempc = c + a;
        c |= 0xFFFF;
        if (c >= tempc) c -= 0x8000;
        c = c << ct;
        byteout();
        c = c << ct;
        byteout();
        if (buf.size() > 1 && buf.back() == 0xFF) buf.pop_back();
        // buf[0] stays the dummy byte; callers read buf[1..).
    }
};

// ---- MQ decoder (T.800 Annex C.3; mirrors codec/mq.py MQDecoder) ----
//
// The segment is attacker-controlled (its length comes from a packet
// header): bytes past its end read as 0xFF, exactly as mq.py's _byte
// does, so a truncated or garbage stream decodes to the same decisions
// as the Python decoder and never reads out of bounds. C is kept to 32
// bits; every value the decoder observes depends on those bits alone.

struct MQDec {
    const uint8_t* data;
    int64_t len;
    int64_t bp = 0;
    uint32_t a = 0x8000, c = 0;
    int ct = 0;
    uint8_t idx[N_CTX];
    uint8_t mps[N_CTX];

    MQDec(const uint8_t* d, int64_t n) : data(d), len(n) {
        std::memset(idx, 0, sizeof(idx));
        std::memset(mps, 0, sizeof(mps));
        idx[0] = 4;
        idx[CTX_RL] = 3;
        idx[CTX_UNIFORM] = 46;
        c = byte(0) << 16;
        bytein();
        c <<= 7;
        ct -= 7;
    }

    uint32_t byte(int64_t i) const { return i < len ? data[i] : 0xFFu; }

    void bytein() {
        if (byte(bp) == 0xFF) {
            if (byte(bp + 1) > 0x8F) {
                c += 0xFF00;
                ct = 8;
            } else {
                bp++;
                c += byte(bp) << 9;
                ct = 7;
            }
        } else {
            bp++;
            c += byte(bp) << 8;
            ct = 8;
        }
    }

    void renorm() {
        do {
            if (ct == 0) bytein();
            a = (a << 1) & 0xFFFF;
            c <<= 1;
            ct--;
        } while (!(a & 0x8000));
    }

    int decode(int ctx) {
        const QeRow& row = QE[idx[ctx]];
        const uint32_t qe = row.qe;
        int d;
        a -= qe;
        if ((c >> 16) < qe) {            // LPS exchange
            if (a < qe) {
                d = mps[ctx];
                idx[ctx] = row.nmps;
            } else {
                d = 1 - mps[ctx];
                if (row.sw) mps[ctx] ^= 1;
                idx[ctx] = row.nlps;
            }
            a = qe;
            renorm();
        } else {
            c -= qe << 16;
            if (!(a & 0x8000)) {         // MPS exchange
                if (a < qe) {
                    d = 1 - mps[ctx];
                    if (row.sw) mps[ctx] ^= 1;
                    idx[ctx] = row.nlps;
                } else {
                    d = mps[ctx];
                    idx[ctx] = row.nmps;
                }
                renorm();
            } else {
                d = mps[ctx];
            }
        }
        return d;
    }
};

// ---- Context tables (T.800 Tables D.1-D.4; mirror codec/t1.py) ----

struct Tables {
    uint8_t zc_ll_lh[3][3][5];
    uint8_t zc_hh[3][3][5];
    uint8_t sc_ctx[3][3];
    uint8_t sc_xor[3][3];

    Tables() {
        for (int sh = 0; sh < 3; sh++)
            for (int sv = 0; sv < 3; sv++)
                for (int sd = 0; sd < 5; sd++) {
                    int c;
                    if (sh == 2) c = 8;
                    else if (sh == 1) c = sv >= 1 ? 7 : (sd >= 1 ? 6 : 5);
                    else {
                        if (sv == 2) c = 4;
                        else if (sv == 1) c = 3;
                        else c = sd >= 2 ? 2 : (sd == 1 ? 1 : 0);
                    }
                    zc_ll_lh[sh][sv][sd] = (uint8_t)c;
                    int hv = sh + sv;
                    if (sd >= 3) c = 8;
                    else if (sd == 2) c = hv >= 1 ? 7 : 6;
                    else if (sd == 1) c = hv >= 2 ? 5 : (hv == 1 ? 4 : 3);
                    else c = hv >= 2 ? 2 : (hv == 1 ? 1 : 0);
                    zc_hh[sh][sv][sd] = (uint8_t)c;
                }
        // Sign coding (Table D.3), indexed [h+1][v+1].
        for (int h = -1; h <= 1; h++)
            for (int v = -1; v <= 1; v++) {
                int ctx, x;
                if (h == 1)      { ctx = v == 1 ? 13 : (v == 0 ? 12 : 11); x = 0; }
                else if (h == 0) { ctx = v == 0 ? 9 : 10; x = v == -1 ? 1 : 0; }
                else             { ctx = v == 1 ? 11 : (v == 0 ? 12 : 13); x = 1; }
                sc_ctx[h + 1][v + 1] = (uint8_t)ctx;
                sc_xor[h + 1][v + 1] = (uint8_t)x;
            }
    }
};

static const Tables T;

// ---- Block coder (T.800 Annex D; mirrors codec/t1.py) ----

struct PassRec {
    int32_t type;      // 0=sigprop 1=magref 2=cleanup
    int32_t plane;
    int64_t cum_len;
    double dist;
};

struct BlockOut {
    std::vector<uint8_t> data;
    int32_t nbps = 0;
    std::vector<PassRec> passes;
};

// Band class: 0 = LL/LH table, 1 = HH table, 2 = HL (LL/LH with H/V swap).
// fracs: optional FRAC_BITS(=7) fractional magnitude bits below the index
// (quantize_fp), null when indices are exact (reversible path).
// floor: lowest bit-plane to code (0 = all). Planes below the floor are
// simply absent from the pass list — a valid truncation the rate
// allocator would have made anyway (the caller guarantees the floor sits
// below the final PCRD cut); the magnitudes' low bits must already be
// zero there (the packed payload never ships them).
static void encode_block(const uint32_t* mags, const uint8_t* negs,
                         const uint8_t* fracs,
                         int h, int w, int bandcls, int floor,
                         BlockOut& out) {
    uint32_t maxv = 0;
    const int n = h * w;
    for (int i = 0; i < n; i++) maxv = mags[i] > maxv ? mags[i] : maxv;
    int nbps = 0;
    while ((1u << nbps) <= maxv && nbps < 32) nbps++;
    out.nbps = nbps;
    if (nbps == 0) return;

    // Padded state arrays (h+2)x(w+2) kill all bounds checks.
    const int pw = w + 2;
    std::vector<uint8_t> sigma((h + 2) * pw, 0);
    std::vector<uint8_t> pi((h + 2) * pw, 0);
    std::vector<uint8_t> refined((h + 2) * pw, 0);
    std::vector<int8_t> chi((h + 2) * pw, 0);   // 0 / +1 / -1 if significant
    auto P = [pw](int y, int x) { return (y + 1) * pw + (x + 1); };

    const bool swap_hv = bandcls == 2;
    const auto& zc = bandcls == 1 ? T.zc_hh : T.zc_ll_lh;

    MQEnc mq;

    auto nbr_sums = [&](int y, int x, int& sh, int& sv, int& sd) {
        const int p = P(y, x);
        sh = sigma[p - 1] + sigma[p + 1];
        sv = sigma[p - pw] + sigma[p + pw];
        sd = sigma[p - pw - 1] + sigma[p - pw + 1] +
             sigma[p + pw - 1] + sigma[p + pw + 1];
    };

    auto code_sign = [&](int y, int x) {
        const int p = P(y, x);
        int hc = chi[p - 1] + chi[p + 1];
        int vc = chi[p - pw] + chi[p + pw];
        hc = hc > 1 ? 1 : (hc < -1 ? -1 : hc);
        vc = vc > 1 ? 1 : (vc < -1 ? -1 : vc);
        int neg = negs[y * w + x] ? 1 : 0;
        mq.encode(neg ^ T.sc_xor[hc + 1][vc + 1], T.sc_ctx[hc + 1][vc + 1]);
    };

    auto set_sig = [&](int y, int x) {
        const int p = P(y, x);
        sigma[p] = 1;
        chi[p] = negs[y * w + x] ? -1 : 1;
    };

    // True magnitude in index units: coded index + retained fractional
    // bits (quantize_fp; exact when fracs is null — reversible path).
    // Accurate tv matters: PCRD ranks passes by slope, and a fixed +0.5
    // midpoint mis-ranks blocks whose slopes cluster (chroma noise),
    // splitting rate badly across components. Mirrors codec/t1.py.
    // Must match bucketeer_tpu.codec.quant.FRAC_BITS (= 7): fracs carry
    // 2^FRAC_BITS sub-index steps. Checked against the Python coder by
    // tests/test_native_t1.py.
    constexpr double FRAC_SCALE = 128.0;
    auto true_val = [&](int y, int x) -> double {
        int64_t v = mags[y * w + x];
        return (double)v + (fracs ? fracs[y * w + x] / FRAC_SCALE : 0.0);
    };

    auto sig_dist = [&](int y, int x, int p) -> double {
        int64_t v = mags[y * w + x];
        int64_t vb = (v >> p) << p;
        double tv = true_val(y, x);
        double r = (double)vb + (double)(1ll << p) * 0.5;
        double d = tv - r;
        return tv * tv - d * d;
    };

    auto ref_dist = [&](int y, int x, int p) -> double {
        int64_t v = mags[y * w + x];
        int64_t v1 = (v >> (p + 1)) << (p + 1);
        double r1 = (double)v1 + (double)(1ll << (p + 1)) * 0.5;
        int64_t v0 = (v >> p) << p;
        double r0 = (double)v0 + (double)(1ll << p) * 0.5;
        double tv = true_val(y, x);
        double d1 = tv - r1, d0 = tv - r0;
        return d1 * d1 - d0 * d0;
    };

    auto zc_ctx = [&](int y, int x) -> int {
        int sh, sv, sd;
        nbr_sums(y, x, sh, sv, sd);
        if (swap_hv) { int t = sh; sh = sv; sv = t; }
        return zc[sh][sv][sd];
    };

    double dist;
    for (int p = nbps - 1; p >= floor; p--) {
        const uint32_t bit = 1u << p;
        const bool first_plane = p == nbps - 1;

        if (!first_plane) {
            // Pass 1: significance propagation.
            dist = 0.0;
            for (int y0 = 0; y0 < h; y0 += 4) {
                const int ymax = y0 + 4 < h ? y0 + 4 : h;
                for (int x = 0; x < w; x++)
                    for (int y = y0; y < ymax; y++) {
                        if (sigma[P(y, x)]) continue;
                        int sh, sv, sd;
                        nbr_sums(y, x, sh, sv, sd);
                        if (sh + sv + sd == 0) continue;
                        if (swap_hv) { int t = sh; sh = sv; sv = t; }
                        int b = (mags[y * w + x] & bit) ? 1 : 0;
                        mq.encode(b, zc[sh][sv][sd]);
                        pi[P(y, x)] = 1;
                        if (b) {
                            set_sig(y, x);
                            dist += sig_dist(y, x, p);
                            code_sign(y, x);
                        }
                    }
            }
            out.passes.push_back({0, p, mq.trunc_length(), dist});

            // Pass 2: magnitude refinement.
            dist = 0.0;
            for (int y0 = 0; y0 < h; y0 += 4) {
                const int ymax = y0 + 4 < h ? y0 + 4 : h;
                for (int x = 0; x < w; x++)
                    for (int y = y0; y < ymax; y++) {
                        const int pp = P(y, x);
                        if (!sigma[pp] || pi[pp]) continue;
                        int ctx;
                        if (refined[pp]) ctx = 16;
                        else {
                            int sh, sv, sd;
                            nbr_sums(y, x, sh, sv, sd);
                            ctx = (sh + sv + sd) ? 15 : 14;
                        }
                        mq.encode((mags[y * w + x] & bit) ? 1 : 0, ctx);
                        dist += ref_dist(y, x, p);
                        refined[pp] = 1;
                    }
            }
            out.passes.push_back({1, p, mq.trunc_length(), dist});
        }

        // Pass 3: cleanup.
        dist = 0.0;
        for (int y0 = 0; y0 < h; y0 += 4) {
            const int ymax = y0 + 4 < h ? y0 + 4 : h;
            for (int x = 0; x < w; x++) {
                int y = y0;
                if (y0 + 3 < h) {
                    bool rl = true;
                    for (int yy = y0; yy < y0 + 4 && rl; yy++) {
                        const int pp = P(yy, x);
                        if (sigma[pp] || pi[pp]) { rl = false; break; }
                        int sh, sv, sd;
                        nbr_sums(yy, x, sh, sv, sd);
                        if (sh + sv + sd != 0) rl = false;
                    }
                    if (rl) {
                        int k = -1;
                        for (int yy = 0; yy < 4; yy++)
                            if (mags[(y0 + yy) * w + x] & bit) { k = yy; break; }
                        if (k < 0) {
                            mq.encode(0, CTX_RL);
                            continue;
                        }
                        mq.encode(1, CTX_RL);
                        mq.encode((k >> 1) & 1, CTX_UNIFORM);
                        mq.encode(k & 1, CTX_UNIFORM);
                        const int yk = y0 + k;
                        set_sig(yk, x);
                        dist += sig_dist(yk, x, p);
                        code_sign(yk, x);
                        y = yk + 1;
                    }
                }
                for (int yy = y; yy < ymax; yy++) {
                    const int pp = P(yy, x);
                    if (sigma[pp] || pi[pp]) continue;
                    int b = (mags[yy * w + x] & bit) ? 1 : 0;
                    mq.encode(b, zc_ctx(yy, x));
                    if (b) {
                        set_sig(yy, x);
                        dist += sig_dist(yy, x, p);
                        code_sign(yy, x);
                    }
                }
            }
        }
        out.passes.push_back({2, p, mq.trunc_length(), dist});
        std::fill(pi.begin(), pi.end(), 0);
    }

    mq.flush();
    out.data.assign(mq.buf.begin() + 1, mq.buf.end());
    const int64_t total = (int64_t)out.data.size();
    for (auto& pr : out.passes)
        if (pr.cum_len > total) pr.cum_len = total;
}

// ---- Block decoder (T.800 Annex D; mirrors codec/decode/t1_dec.py) ----

// Per-block status of t1_decode_blocks; t1_dec.py raises the DecodeError
// decode_block raises for the same input.
enum DecodeStatus {
    DEC_OK = 0,
    DEC_TOO_MANY_PLANES = 1,   // nbps > 30
    DEC_TOO_MANY_PASSES = 2,   // npasses > 3 * nbps - 2
    DEC_OVERFLOW = 3,          // a half-magnitude >= 2^31
};

// One code-block's pass stream -> (h, w) signed half-magnitudes
// |hval| = (2m + 1) << p, row-major in out. Stops after npasses passes.
static int decode_block(const uint8_t* data, int64_t len, int nbps,
                        int npasses, int bandcls, int h, int w,
                        int32_t* out, int64_t& n_dec) {
    n_dec = 0;
    const int64_t size = (int64_t)h * w;
    std::fill(out, out + size, 0);
    if (nbps <= 0 || npasses <= 0) return DEC_OK;
    if (nbps > 30) return DEC_TOO_MANY_PLANES;
    if (npasses > 3 * nbps - 2) return DEC_TOO_MANY_PASSES;

    // Neighbour-significance counters, bumped only when a sample becomes
    // significant (bounded by 2, 2 and 4, so they index the ZC tables).
    std::vector<uint8_t> sigma(size, 0), pi(size, 0), refined(size, 0),
        neg(size, 0), nbh(size, 0), nbv(size, 0), nbd(size, 0);
    std::vector<int64_t> habs(size, 0);

    const bool swap_hv = bandcls == 2;
    const auto& zc = bandcls == 1 ? T.zc_hh : T.zc_ll_lh;
    auto zc_ctx = [&](int64_t i) -> int {
        return swap_hv ? zc[nbv[i]][nbh[i]][nbd[i]]
                       : zc[nbh[i]][nbv[i]][nbd[i]];
    };

    MQDec mq(data, len);

    auto set_sig = [&](int64_t i, int y, int x) {
        sigma[i] = 1;
        if (x > 0) {
            nbh[i - 1]++;
            if (y > 0) nbd[i - 1 - w]++;
            if (y < h - 1) nbd[i - 1 + w]++;
        }
        if (x < w - 1) {
            nbh[i + 1]++;
            if (y > 0) nbd[i + 1 - w]++;
            if (y < h - 1) nbd[i + 1 + w]++;
        }
        if (y > 0) nbv[i - w]++;
        if (y < h - 1) nbv[i + w]++;
    };

    auto decode_sign = [&](int64_t i, int y, int x) -> uint8_t {
        int hc = 0, vc = 0;
        if (x > 0 && sigma[i - 1]) hc += neg[i - 1] ? -1 : 1;
        if (x < w - 1 && sigma[i + 1]) hc += neg[i + 1] ? -1 : 1;
        if (y > 0 && sigma[i - w]) vc += neg[i - w] ? -1 : 1;
        if (y < h - 1 && sigma[i + w]) vc += neg[i + w] ? -1 : 1;
        hc = hc > 1 ? 1 : (hc < -1 ? -1 : hc);
        vc = vc > 1 ? 1 : (vc < -1 ? -1 : vc);
        return (uint8_t)(mq.decode(T.sc_ctx[hc + 1][vc + 1]) ^
                         T.sc_xor[hc + 1][vc + 1]);
    };

    int left = npasses;
    bool first_plane = true;
    for (int p = nbps - 1; p >= 0; p--) {
        const int64_t bit3 = (int64_t)3 << p;
        const int64_t bit1 = (int64_t)1 << p;

        if (!first_plane) {
            // Pass 1: significance propagation.
            for (int y0 = 0; y0 < h; y0 += 4) {
                const int ymax = y0 + 4 < h ? y0 + 4 : h;
                for (int x = 0; x < w; x++) {
                    int64_t i = (int64_t)y0 * w + x;
                    for (int y = y0; y < ymax; y++, i += w) {
                        if (sigma[i] || !(nbh[i] | nbv[i] | nbd[i]))
                            continue;
                        n_dec++;
                        pi[i] = 1;
                        if (mq.decode(zc_ctx(i))) {
                            n_dec++;
                            neg[i] = decode_sign(i, y, x);
                            set_sig(i, y, x);
                            habs[i] = bit3;
                        }
                    }
                }
            }
            if (--left == 0) break;

            // Pass 2: magnitude refinement.
            for (int y0 = 0; y0 < h; y0 += 4) {
                const int ymax = y0 + 4 < h ? y0 + 4 : h;
                for (int x = 0; x < w; x++) {
                    int64_t i = (int64_t)y0 * w + x;
                    for (int y = y0; y < ymax; y++, i += w) {
                        if (!sigma[i] || pi[i]) continue;
                        int ctx;
                        if (refined[i]) ctx = 16;
                        else ctx = (nbh[i] | nbv[i] | nbd[i]) ? 15 : 14;
                        n_dec++;
                        if (mq.decode(ctx)) habs[i] += bit1;
                        else habs[i] -= bit1;
                        refined[i] = 1;
                    }
                }
            }
            if (--left == 0) break;
        }

        // Pass 3: cleanup, with the run-length shortcut.
        for (int y0 = 0; y0 < h; y0 += 4) {
            const int ymax = y0 + 4 < h ? y0 + 4 : h;
            for (int x = 0; x < w; x++) {
                const int64_t i0 = (int64_t)y0 * w + x;
                int y = y0;
                if (y0 + 3 < h) {
                    bool rl = true;
                    int64_t i = i0;
                    for (int k = 0; k < 4; k++, i += w)
                        if (sigma[i] | pi[i] | nbh[i] | nbv[i] | nbd[i]) {
                            rl = false;
                            break;
                        }
                    if (rl) {
                        n_dec++;
                        if (!mq.decode(CTX_RL)) continue;
                        n_dec += 2;
                        int k = mq.decode(CTX_UNIFORM) << 1;
                        k |= mq.decode(CTX_UNIFORM);
                        const int yk = y0 + k;
                        const int64_t ik = i0 + (int64_t)k * w;
                        n_dec++;
                        neg[ik] = decode_sign(ik, yk, x);
                        set_sig(ik, yk, x);
                        habs[ik] = bit3;
                        y = yk + 1;
                    }
                }
                int64_t i = i0 + (int64_t)(y - y0) * w;
                for (int yy = y; yy < ymax; yy++, i += w) {
                    if (sigma[i] || pi[i]) continue;
                    n_dec++;
                    if (mq.decode(zc_ctx(i))) {
                        n_dec++;
                        neg[i] = decode_sign(i, yy, x);
                        set_sig(i, yy, x);
                        habs[i] = bit3;
                    }
                }
            }
        }
        if (--left == 0) break;
        std::fill(pi.begin(), pi.end(), 0);
        first_plane = false;
    }

    for (int64_t i = 0; i < size; i++) {
        if (habs[i] >= ((int64_t)1 << 31)) return DEC_OVERFLOW;
        out[i] = neg[i] ? -(int32_t)habs[i] : (int32_t)habs[i];
    }
    return DEC_OK;
}

struct T1Result {
    std::vector<BlockOut> blocks;
};

template <typename F>
void run_pool(int n_blocks, int n_threads, F&& body) {
    std::atomic<int> next(0);
    auto worker = [&]() {
        for (;;) {
            int i = next.fetch_add(1);
            if (i >= n_blocks) break;
            body(i);
        }
    };
    if (n_threads <= 1 || n_blocks <= 1) {
        worker();
    } else {
        std::vector<std::thread> pool;
        int nt = n_threads < n_blocks ? n_threads : n_blocks;
        for (int t = 0; t < nt; t++) pool.emplace_back(worker);
        for (auto& th : pool) th.join();
    }
}

}  // namespace

extern "C" {

// Bumped whenever any exported signature changes; the Python loader
// refuses a library whose version doesn't match, so a stale prebuilt
// .so (deployment images may prune t1.cpp) fails loudly instead of
// misreading the new argument layout.
int32_t t1_abi_version() { return 6; }

T1Result* t1_encode_blocks(int n_blocks,
                           const uint32_t* mags, const uint8_t* negs,
                           const uint8_t* fracs,
                           const int64_t* offsets,
                           const int32_t* hs, const int32_t* ws,
                           const int32_t* bandcls, int n_threads) {
    auto* res = new T1Result();
    res->blocks.resize(n_blocks);
    run_pool(n_blocks, n_threads, [&](int i) {
        encode_block(mags + offsets[i], negs + offsets[i],
                     fracs ? fracs + offsets[i] : nullptr,
                     hs[i], ws[i], bandcls[i], 0, res->blocks[i]);
    });
    return res;
}

// Packed-bitmap entry (the device front-end path, codec/frontend.py).
// payload: concatenated 512-byte rows; block i's rows start at byte
// offsets[i]*512: [sign bitmap][plane nbps[i]-1]...[plane floors[i]].
// Bitmaps are 64x64 LSB-first: sample (y,x) -> byte y*8 + x/8, bit x%8;
// a partial (h,w) block occupies the top-left corner. Blocks with
// nbps <= floors ship no rows and code as empty.
T1Result* t1_encode_packed(int n_blocks, const uint8_t* payload,
                           const int64_t* offsets,
                           const int32_t* nbps, const int32_t* floors,
                           const int32_t* hs, const int32_t* ws,
                           const int32_t* bandcls, int n_threads) {
    auto* res = new T1Result();
    res->blocks.resize(n_blocks);
    run_pool(n_blocks, n_threads, [&](int i) {
        const int nbp = nbps[i], floor = floors[i];
        if (nbp <= floor) return;             // dead block: zero passes
        const int h = hs[i], w = ws[i];
        const uint8_t* rows = payload + offsets[i] * 512;
        uint32_t mags[64 * 64];
        uint8_t negs[64 * 64];
        std::memset(mags, 0, sizeof(uint32_t) * h * w);
        for (int y = 0; y < h; y++)
            for (int x = 0; x < w; x++)
                negs[y * w + x] = (rows[y * 8 + (x >> 3)] >> (x & 7)) & 1;
        for (int j = 0, p = nbp - 1; p >= floor; j++, p--) {
            const uint8_t* bm = rows + (1 + j) * 512;
            for (int y = 0; y < h; y++)
                for (int x = 0; x < w; x++)
                    mags[y * w + x] |=
                        (uint32_t)((bm[y * 8 + (x >> 3)] >> (x & 7)) & 1)
                        << p;
        }
        encode_block(mags, negs, nullptr, h, w, bandcls[i], floor,
                     res->blocks[i]);
    });
    return res;
}

// CX/D replay entry (the device context-modeling path, codec/cxd.py):
// the device already ran significance propagation / magnitude
// refinement / cleanup and shipped the ordered (context, decision)
// symbol stream; the host just replays it through the MQ coder — no
// neighborhood state, no bit-plane walks. payload: 384-byte rows of
// 6-bit symbols, four per little-endian 24-bit group, symbol = ctx
// (low 5 bits) | decision << 5; block i's rows start at
// row_offsets[i]*384. Pass metadata is flat across blocks: block i owns
// passes [pass_offsets[i], pass_offsets[i+1]) with per-pass symbol
// counts, types/planes for the PassInfo table, and the device-computed
// exact distortion reductions passed straight through. nbps[i] is the
// block's coded bit-plane count (the stream itself no longer reveals
// it). Blocks with zero passes code as empty (nbps forced 0, like a
// dead packed block).
T1Result* t1_encode_cxd(int n_blocks, const uint8_t* payload,
                        const int64_t* row_offsets,
                        const int32_t* nbps,
                        const int64_t* pass_offsets,
                        const int32_t* pass_types,
                        const int32_t* pass_planes,
                        const int32_t* pass_nsyms,
                        const double* pass_dists, int n_threads) {
    auto* res = new T1Result();
    res->blocks.resize(n_blocks);
    run_pool(n_blocks, n_threads, [&](int i) {
        BlockOut& out = res->blocks[i];
        const int64_t p0 = pass_offsets[i], p1 = pass_offsets[i + 1];
        if (p1 <= p0) return;               // dead block: zero passes
        const uint8_t* rows = payload + row_offsets[i] * 384;
        MQEnc mq;
        int64_t sym = 0;
        uint32_t word = 0;
        for (int64_t j = p0; j < p1; j++) {
            for (int32_t s = 0; s < pass_nsyms[j]; s++, sym++) {
                const int r = (int)(sym & 3);
                if (r == 0) {       // one load per 4-symbol group
                    const uint8_t* g = rows + (sym >> 2) * 3;
                    word = (uint32_t)g[0] | ((uint32_t)g[1] << 8) |
                           ((uint32_t)g[2] << 16);
                }
                const uint32_t cxd = (word >> (6 * r)) & 63u;
                mq.encode((int)(cxd >> 5), (int)(cxd & 31u));
            }
            out.passes.push_back({pass_types[j], pass_planes[j],
                                  mq.trunc_length(), pass_dists[j]});
        }
        mq.flush();
        out.nbps = nbps[i];
        out.data.assign(mq.buf.begin() + 1, mq.buf.end());
        const int64_t total = (int64_t)out.data.size();
        for (auto& pr : out.passes)
            if (pr.cum_len > total) pr.cum_len = total;
    });
    return res;
}

// Decode entry (the read path, codec/decode/t1_dec.py). Block i's
// segment is data[offsets[i], offsets[i+1]); its hs[i] x ws[i] signed
// half-magnitudes are written row-major at out + out_offsets[i], its MQ
// decision count to decisions[i] and a DecodeStatus to status[i].
// Results go straight into caller-owned arrays: no handle to free.
void t1_decode_blocks(int n_blocks, const uint8_t* data,
                      const int64_t* offsets,
                      const int32_t* nbps, const int32_t* npasses,
                      const int32_t* bandcls,
                      const int32_t* hs, const int32_t* ws,
                      int32_t* out, const int64_t* out_offsets,
                      int64_t* decisions, int32_t* status,
                      int n_threads) {
    run_pool(n_blocks, n_threads, [&](int i) {
        status[i] = decode_block(data + offsets[i],
                                 offsets[i + 1] - offsets[i], nbps[i],
                                 npasses[i], bandcls[i], hs[i], ws[i],
                                 out + out_offsets[i], decisions[i]);
    });
}

void t1_block_sizes(T1Result* r, int32_t* nbps, int32_t* npasses,
                    int64_t* nbytes) {
    for (size_t i = 0; i < r->blocks.size(); i++) {
        nbps[i] = r->blocks[i].nbps;
        npasses[i] = (int32_t)r->blocks[i].passes.size();
        nbytes[i] = (int64_t)r->blocks[i].data.size();
    }
}

// Copies every block's result into caller-owned flat arrays in one
// call: block i's bytes land at data + (sum of nbytes[j < i]) and its
// passes at index (sum of npasses[j < i]) of the four pass arrays, the
// prefix sums of what t1_block_sizes reports.
void t1_result_copy(T1Result* r, uint8_t* data, int32_t* ptype,
                    int32_t* pplane, int64_t* plen, double* pdist) {
    int64_t b0 = 0, k0 = 0;
    for (const BlockOut& b : r->blocks) {
        if (!b.data.empty())
            std::memcpy(data + b0, b.data.data(), b.data.size());
        b0 += (int64_t)b.data.size();
        for (const PassRec& pr : b.passes) {
            ptype[k0] = pr.type;
            pplane[k0] = pr.plane;
            plen[k0] = pr.cum_len;
            pdist[k0] = pr.dist;
            k0++;
        }
    }
}

void t1_result_free(T1Result* r) { delete r; }

}  // extern "C"
