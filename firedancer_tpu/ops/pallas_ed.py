"""Fused Pallas TPU kernels for batched ed25519 verification.

This is the high-throughput backend of the sigverify hot loop — the role
the AVX-512-IFMA backend plays for the reference
(ref: src/ballet/ed25519/avx512/fd_r43x6.h:10-32, fd_r43x6_ge.c) and the
wiredancer FPGA plays at the tile level (ref: src/wiredancer/README.md:99-119).
The pure-jnp kernels in ops/ed25519.py remain the portable reference
implementation (and the CPU-backend path); these kernels compute the same
function but keep the entire field/point computation resident in VMEM, so
the ~3k field multiplies per signature never round-trip HBM. On the XLA
path each fe.mul materializes a (20,20,B) outer product to HBM, which
measures ~55 ns/lane; in-kernel the same multiply is ~1.3 ns/lane.

Layout: field elements are (NLIMB, TB) int32 limb-major blocks (batch in
the lane dimension, limbs in sublanes), radix 2^13, same representation
and bound discipline as ops/fe25519.py (see its module docstring for the
carry analysis). The grid splits the batch into TB-lane programs. The
live-lane count (a traced scalar prefetched to SMEM; the whole batch
when the caller gives none) guards each program: those that hold only
padding run nothing, so a part-full batch costs what its live programs
cost under the one compiled shape.

One fused kernel (`_verify_kernel`, r4 — previously decompress and
dsm+encode were two dispatches with an HBM bounce of x/t between them):
  * RFC 8032 §5.1.3 point decompression with failure masks; one
    (p-5)/8 power chain (addition-chain form: 254 squarings + 11
    multiplies instead of scan square-and-multiply);
  * the double scalar mul [S]B + [k](−A) with 4-bit windows
    (fixed-base: doubling-free precomputed affine tables, 7-mul mixed
    adds; variable-base: per-lane 16-entry table, 256 doublings in
    T-free 7-mul form where possible);
  * the projective→affine encode (one inversion chain) compared
    in-kernel against R's exact 255-bit digits + sign bit — digit
    equality on canonical output is exactly byte equality of the
    canonical encoding, so no byte packing leaves the chip.

Glue `verify_batch` reproduces ops/ed25519.verify_batch semantics
bit-for-bit (strict small-order rejection, S canonicality, cofactorless
equation) with SHA-512 and scalar reduction still on the jnp path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import fe25519 as fe
from . import ed25519 as ed

NL = fe.NLIMB
BITS = fe.BITS
MASK = fe.MASK
FOLD = fe.FOLD
P = fe.P

DEFAULT_TB = 256


# ---------------------------------------------------------------------------
# in-kernel field arithmetic on (NL, TB) int32 values
# ---------------------------------------------------------------------------

def _carry(x, passes=3):
    """Relaxed parallel carry; bound analysis in ops/fe25519.py."""
    for _ in range(passes):
        lo = x & MASK
        hi = x >> BITS
        x = lo + jnp.concatenate([hi[-1:] * FOLD, hi[:-1]], axis=0)
    return x


def _const_col(arr) -> jnp.ndarray:
    """(NL,) numpy constant -> (NL, 1) broadcastable column.

    Built from broadcasted_iota + scalar selects rather than a literal
    array: Pallas TPU kernels may not capture non-scalar array constants
    (they would have to be passed as inputs), but scalar splats are fine
    and Mosaic folds this chain at compile time. (General-width form:
    _const_rows, defined with the scalar machinery below.)"""
    return _const_rows(arr, NL)


_SUB_C = None     # initialized lazily to avoid import-order issues
_ONE = None


def fadd(a, b):
    # Kernel-wide loose bound B = 10624 (r10 carry tightening): every fe
    # value entering fmul has limbs in [0, B], and multiply safety is
    # 20·B² ≈ 2^31.07 < 2^32 — past int32 max but inside the
    # wrap-tolerant uint32 window _reduce39's 2-pass carry recovers
    # (B may grow to ⌊√(2^32/20)⌋ = 14654 before that window closes,
    # so 10624 carries ~1.4× slack). fadd: 2B < 2^15, one pass leaves
    # limbs ≤ 8193 and limb0 ≤ 8191 + 2·608 = 9407 ≤ B.
    return _carry(a + b, passes=1)


def fsub(a, b):
    # a + C − b with C ≡ 0 (mod p), per-limb 22752..24573 > B so the
    # difference stays non-negative limb-wise; sum ≤ B + 24573 = 35197,
    # ONE pass (r10 — previously two) leaves limbs ≤ 8195 and
    # limb0 ≤ 8191 + 608·(35197>>13) = 10623 ≤ B: the loose bound is
    # DEFINED by this worst case (tests/test_pallas_bounds.py), and
    # the dropped pass is ~60 elem-ops off every subtraction in the
    # point formulas (~8% of the dsm budget).
    return _carry(a + _const_col(fe.SUB_C) - b, passes=1)


def fneg(a):
    # the b=0 case of fsub's expression: sup 24573, one pass leaves
    # limb0 ≤ 8191 + 608·2 = 9407 ≤ B
    return _carry(_const_col(fe.SUB_C) - a, passes=1)


def fmul_small2(a):
    """a·2 for loose a — one pass suffices."""
    return _carry(a * 2, passes=1)


_HI_MASK = (1 << (32 - BITS)) - 1


def _reduce39(c):
    """(2*NL-1, TB) schoolbook coefficients -> loose (NL, TB).

    Coefficients are sums of up to 20 limb products; with the kernel-wide
    loose bound B = 10624 (see the invariant note on fadd) they reach
    20·B² ≈ 2^31.07 — past int32 max but below 2^32, so the int32
    accumulation wraps. Two's complement keeps the low bits exact:
    `c & MASK` is already the true low 13 bits, and masking the
    arithmetic shift to its low 19 bits recovers the true logical
    `hi = c >> 13` (true hi < 2^19 because the true value < 2^32).

    Two carry passes then restore the loose bound: input rows to the
    carry are < 2^27.4 (hi ≤ 275560 from 20·B², row ≤ lo+hi ≤ 283751,
    ×FOLD(608) + row ≤ 1.73e8); pass 1 leaves limbs ≤ 29251 and
    limb0 ≤ 8191 + 608·(x₁₉>>13) ≤ 649631; pass 2 leaves limb1 ≤ 8270,
    limb0 ≤ 8799, others ≤ 8195 — all ≤ B, closing the invariant.
    (tests/test_pallas_bounds.py walks these intervals mechanically.)
    """
    lo = c & MASK
    hi = (c >> BITS) & _HI_MASK
    z1 = jnp.zeros_like(lo[:1])
    c = (jnp.concatenate([lo, z1], axis=0)
         + jnp.concatenate([z1, hi], axis=0))          # (2*NL, TB)
    return _carry(c[:NL] + c[NL:] * FOLD, passes=2)


_ROLL = pltpu.roll     # tests swap in jnp.roll to run kernels as pure
                       # jnp on CPU (bit-identical: the rotated-in top
                       # rows are always zeros here)


def fmul(a, b):
    """Schoolbook product, row-broadcast pad+roll form: 20 shifted
    (2*NL,TB)-wide accumulations, entirely in VMEM — no HBM
    intermediates, no gathers."""
    tb = a.shape[-1]
    acc = jnp.zeros((2 * NL, tb), jnp.int32)
    znl = jnp.zeros((NL, tb), jnp.int32)
    for i in range(NL):
        prod = a[i][None, :] * b                       # (NL, TB)
        padded = jnp.concatenate([prod, znl], axis=0)  # (2*NL, TB)
        acc = acc + _ROLL(padded, shift=i, axis=0)
    return _reduce39(acc[: 2 * NL - 1])


def fsq(a):
    return fmul(a, a)


def fmul_const(a, const_limbs):
    """Multiply by a (NL,) constant limb vector (e.g. d, 2d, sqrt(-1)):
    schoolbook with python-int scalar rows (splat constants only)."""
    tb = a.shape[-1]
    acc = jnp.zeros((2 * NL, tb), jnp.int32)
    znl = jnp.zeros((NL, tb), jnp.int32)
    for i, v in enumerate(np.asarray(const_limbs, np.int64)):
        if not int(v):
            continue
        padded = jnp.concatenate([jnp.int32(int(v)) * a, znl], axis=0)
        acc = acc + _ROLL(padded, shift=i, axis=0)
    return _reduce39(acc[: 2 * NL - 1])


def _digit_pass(x, fold=False):
    """Sequential exact base-2^13 digit pass on (NL, TB); row ops."""
    c = jnp.zeros_like(x[0:1])
    rows = []
    for i in range(NL):
        v = x[i:i + 1] + c
        rows.append(v & MASK)
        c = v >> BITS
    out = jnp.concatenate(rows, axis=0)
    if fold:
        out = jnp.concatenate([out[0:1] + c * FOLD, out[1:]], axis=0)
    return out


def _flt_const(x, const_digits):
    """Lexicographic x < const on exact digit vectors. (1, TB) bool."""
    c = np.asarray(const_digits)
    lt = jnp.zeros_like(x[0:1], jnp.bool_)
    eq = jnp.ones_like(x[0:1], jnp.bool_)
    for i in range(NL - 1, -1, -1):
        ci = jnp.int32(int(c[i]))
        lt = lt | (eq & (x[i:i + 1] < ci))
        eq = eq & (x[i:i + 1] == ci)
    return lt


def fcanon(x):
    """Exact canonical digits in [0, p). Mirrors fe25519.canonical."""
    x = _carry(x, passes=3)
    x = _digit_pass(x, fold=True)
    x = _digit_pass(x, fold=True)
    hb = 255 - BITS * (NL - 1)                          # 8
    h = x[NL - 1:NL] >> hb
    x = jnp.concatenate(
        [x[0:1] + h * 19, x[1:NL - 1], x[NL - 1:NL] & ((1 << hb) - 1)],
        axis=0)
    x = _digit_pass(x)
    p_col = _const_col(fe.P_LIMBS)
    for _ in range(2):
        ge = ~_flt_const(x, fe.P_LIMBS)
        x = _digit_pass(x - jnp.where(ge, p_col, 0))
    return x


def fis_zero(x):
    return jnp.all(fcanon(x) == 0, axis=0, keepdims=True)


# ---------------------------------------------------------------------------
# power chains (addition-chain form: 254 sq + 11 mul per chain)
# ---------------------------------------------------------------------------

def _nsq(x, n):
    return jax.lax.fori_loop(0, n, lambda i, v: fsq(v), x)


def _chain_z250(x):
    """x^(2^250 - 1) plus intermediates (z50, x11) — shared prefix of the
    standard curve25519 inversion/sqrt addition chain."""
    x2 = fsq(x)
    x4 = fsq(x2)
    x8 = fsq(x4)
    x9 = fmul(x, x8)
    x11 = fmul(x2, x9)
    x22 = fsq(x11)
    z5 = fmul(x9, x22)                   # x^(2^5-1)
    z10 = fmul(_nsq(z5, 5), z5)          # x^(2^10-1)
    z20 = fmul(_nsq(z10, 10), z10)
    z40 = fmul(_nsq(z20, 20), z20)
    z50 = fmul(_nsq(z40, 10), z10)
    z100 = fmul(_nsq(z50, 50), z50)
    z200 = fmul(_nsq(z100, 100), z100)
    z250 = fmul(_nsq(z200, 50), z50)
    return z250, x11


def fpow_p58(x):
    """x^((p-5)/8) = x^(2^252 - 3)."""
    z250, _ = _chain_z250(x)
    return fmul(_nsq(z250, 2), x)


def finv(x):
    """x^(p-2) = x^(2^255 - 21)."""
    z250, x11 = _chain_z250(x)
    return fmul(_nsq(z250, 5), x11)


# ---------------------------------------------------------------------------
# point ops — extended coordinates, precomputed-operand adds
# ---------------------------------------------------------------------------

def pt_dbl_not(p):
    """Doubling without computing T (7 muls) — legal when the result
    feeds another doubling (dbl never reads T)."""
    x1, y1, z1, _ = p
    a = fsq(x1)
    b = fsq(y1)
    c = fmul_small2(fsq(z1))
    h = fadd(a, b)
    e = fsub(h, fsq(fadd(x1, y1)))
    g = fsub(a, b)
    f = fadd(c, g)
    return (fmul(e, f), fmul(g, h), fmul(f, g), p[3])


def pt_dbl_t(p):
    """Full doubling (8 muls)."""
    x1, y1, z1, _ = p
    a = fsq(x1)
    b = fsq(y1)
    c = fmul_small2(fsq(z1))
    h = fadd(a, b)
    e = fsub(h, fsq(fadd(x1, y1)))
    g = fsub(a, b)
    f = fadd(c, g)
    return (fmul(e, f), fmul(g, h), fmul(f, g), fmul(e, h))


def pt_madd_aff(p, q_pre):
    """p + q for q affine precomputed (ymx, ypx, t2d): 7 muls.
    q_pre rows: Y2−X2, Y2+X2, 2d·T2 with Z2=1."""
    x1, y1, z1, t1 = p
    ymx, ypx, t2d = q_pre
    a = fmul(fsub(y1, x1), ymx)
    b = fmul(fadd(y1, x1), ypx)
    c = fmul(t1, t2d)
    d = fmul_small2(z1)
    e = fsub(b, a)
    f = fsub(d, c)
    g = fadd(d, c)
    h = fadd(b, a)
    return (fmul(e, f), fmul(g, h), fmul(f, g), fmul(e, h))


def pt_add_pre(p, q_pre):
    """p + q for q projective precomputed (ymx, ypx, z2x2, t2d): 8 muls."""
    x1, y1, z1, t1 = p
    ymx, ypx, z2x2, t2d = q_pre
    a = fmul(fsub(y1, x1), ymx)
    b = fmul(fadd(y1, x1), ypx)
    c = fmul(t1, t2d)
    d = fmul(z1, z2x2)
    e = fsub(b, a)
    f = fsub(d, c)
    g = fadd(d, c)
    h = fadd(b, a)
    return (fmul(e, f), fmul(g, h), fmul(f, g), fmul(e, h))


def pt_add_full(p, q):
    """General extended add (9 muls) — used once to join the two
    accumulators."""
    x1, y1, z1, t1 = p
    x2, y2, z2, t2 = q
    a = fmul(fsub(y1, x1), fsub(y2, x2))
    b = fmul(fadd(y1, x1), fadd(y2, x2))
    c = fmul(fmul_const(t1, fe.D2_LIMBS), t2)
    d = fmul_small2(fmul(z1, z2))
    e = fsub(b, a)
    f = fsub(d, c)
    g = fadd(d, c)
    h = fadd(b, a)
    return (fmul(e, f), fmul(g, h), fmul(f, g), fmul(e, h))


def pt_identity(tb):
    z = jnp.zeros((NL, tb), jnp.int32)
    one = jnp.concatenate([jnp.ones((1, tb), jnp.int32), z[1:]], axis=0)
    return (z, one, one, z)


def _to_pre(p):
    """Projective entry -> (ymx, ypx, 2·Z, 2d·T) precomputed form."""
    x, y, z, t = p
    return (fsub(y, x), fadd(y, x), fmul_small2(z),
            fmul_const(t, fe.D2_LIMBS))


def _sel16(entries, w):
    """Binary-tree select of 16 table entries (tuples of (NL,TB)) by
    per-lane window value w (1,TB) in [0,16)."""
    ncoord = len(entries[0])
    cur = entries
    for bit in range(4):
        m = ((w >> bit) & 1).astype(jnp.bool_)
        cur = [tuple(jnp.where(m, hi[c], lo[c]) for c in range(ncoord))
               for lo, hi in zip(cur[0::2], cur[1::2])]
    return cur[0]


# ---------------------------------------------------------------------------
# fixed-base table (host-generated, affine precomputed form)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _fb_tables() -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(64,16,NL) int32 ×3: (Y−X, Y+X, 2d·T) of (w·16^j)·B affine.
    w=0 encodes the identity (1, 1, 0)."""
    tab = ed._fixed_base_table()                     # (64,16,4,NL) affine ext
    d2 = 2 * fe.d % P
    ymx = np.zeros((64, 16, NL), np.int32)
    ypx = np.zeros((64, 16, NL), np.int32)
    t2d = np.zeros((64, 16, NL), np.int32)
    for j in range(64):
        for w in range(16):
            x = fe.limbs_to_int(tab[j, w, 0])
            y = fe.limbs_to_int(tab[j, w, 1])
            t = fe.limbs_to_int(tab[j, w, 3])
            ymx[j, w] = fe._int_to_limbs((y - x) % P)
            ypx[j, w] = fe._int_to_limbs((y + x) % P)
            t2d[j, w] = fe._int_to_limbs(t * d2 % P)
    return ymx, ypx, t2d


def _fb_entry(ymx_j, ypx_j, t2d_j, w):
    """Select fb table entry: refs sliced to (16, NL), per-lane w (1,TB).
    Constants broadcast against the batch inside the tree."""
    entries = [
        (ymx_j[k][:, None], ypx_j[k][:, None], t2d_j[k][:, None])
        for k in range(16)
    ]
    return _sel16(entries, w)


# ---------------------------------------------------------------------------
# in-kernel scalar/digit machinery (r5: the byte→digit conversions, the
# mod-l reduction of the sha512 output, and 4-bit window extraction all
# moved from the jnp glue into the fused kernel — the glue's
# bits-matmuls and (64, B) window materializations were ~1/3 of the
# strict path's wall time at batch 8192; row-op mirrors of
# ops/ed25519.py::{sc_reduce64, sc_windows4} and fe25519.frombytes,
# diff-tested against them in tests/test_pallas_ed.py)
# ---------------------------------------------------------------------------

def _const_rows(arr, width) -> jnp.ndarray:
    """(width,) numpy constant -> (width, 1) broadcastable column
    (the general-width form of _const_col, same splat-select
    construction and rationale)."""
    idx = jax.lax.broadcasted_iota(jnp.int32, (width, 1), 0)
    out = jnp.zeros((width, 1), jnp.int32)
    for i, v in enumerate(np.asarray(arr, np.int64)):
        if int(v):
            out = jnp.where(idx == i, jnp.int32(int(v)), out)
    return out


def _bytes_to_digits(b, ndig, mask_top7=False):
    """(nbytes, TB) int32 LE byte rows -> (ndig, TB) exact base-2^13
    digits. Row-op mirror of fe25519.frombytes: digit j takes bits
    [13j, 13j+13), i.e. 2 bytes when 13j%8 <= 3, else 3."""
    nbytes = b.shape[0]
    if mask_top7:
        b = jnp.concatenate([b[:-1], b[-1:] & 0x7F], axis=0)
    rows = []
    for j in range(ndig):
        a, r = divmod(BITS * j, 8)
        if a >= nbytes:
            rows.append(jnp.zeros_like(b[0:1]))
            continue
        v = b[a:a + 1] >> r
        if a + 1 < nbytes:
            v = v | (b[a + 1:a + 2] << (8 - r))
        if r > 3 and a + 2 < nbytes:
            v = v | (b[a + 2:a + 3] << (16 - r))
        rows.append(v & MASK)
    return jnp.concatenate(rows, axis=0)


def _sc_pass(x, width):
    """Sequential exact digit pass on (n, TB) rows -> (width, TB);
    mirror of ed._exact_digit_pass (non-negative value, signed rows)."""
    n = x.shape[0]
    c = jnp.zeros_like(x[0:1])
    rows = []
    for i in range(width):
        v = (x[i:i + 1] + c) if i < n else c
        rows.append(v & MASK)
        c = v >> BITS
    return jnp.concatenate(rows, axis=0)


def _sc_sub_l_if_ge(d):
    ge = ~_flt_const(d, ed.L_DIGITS)
    return _sc_pass(d - jnp.where(ge, _const_rows(ed.L_DIGITS, NL), 0),
                    NL)


def _rows_pad(x, width):
    n = x.shape[0]
    if n >= width:
        return x[:width]
    return jnp.concatenate(
        [x, jnp.zeros((width - n, x.shape[1]), jnp.int32)], axis=0)


def _sc_reduce_rows(d, nd):
    """(nd, TB) exact digits of a value < 2^(13·nd) -> canonical digits
    mod l. Row-op mirror of ed._reduce_digits_mod_l (fold 2^260 ≡
    −256δ, then split at bit 252 and one δ multiply, then two
    conditional subtracts)."""
    tb = d.shape[-1]
    delta = np.asarray(ed.DELTA256_DIGITS, np.int64)
    while nd > 21:
        m = nd - 20
        K = (ed.DELTA256 * (1 << (BITS * m)) + ed.L - 1) // ed.L
        A = K * ed.L
        out_bits = (A + (1 << 260)).bit_length() + 1
        width = -(-out_bits // BITS)
        lo, hi = d[:20], d[20:nd]
        conv_len = m + len(delta) - 1
        conv_rows = []
        for j in range(conv_len):
            acc = None
            for i, dd in enumerate(delta):
                t = j - i
                if 0 <= t < m and int(dd):
                    term = hi[t:t + 1] * jnp.int32(int(dd))
                    acc = term if acc is None else acc + term
            conv_rows.append(acc if acc is not None
                             else jnp.zeros((1, tb), jnp.int32))
        conv = jnp.concatenate(conv_rows, axis=0)
        acc = _rows_pad(lo, width) \
            + _const_rows(ed._int_digits(A, width), width) \
            - _rows_pad(conv, width)
        d = _sc_pass(acc, width)
        nd = width
    if nd == 20:
        d = jnp.concatenate([d, jnp.zeros((1, tb), jnp.int32)], axis=0)
    hi = (d[19:20] >> 5) + (d[20:21] << 8)           # < 2^9
    lo = jnp.concatenate([d[:19], d[19:20] & 31], axis=0)
    sub = jnp.concatenate(
        [hi * jnp.int32(int(ed.DELTA_DIGITS[i])) for i in range(10)]
        + [jnp.zeros((10, tb), jnp.int32)], axis=0)
    z = _sc_pass(lo + _const_rows(ed.L_DIGITS, NL) - sub, NL)
    return _sc_sub_l_if_ge(_sc_sub_l_if_ge(z))


def _win4(d, j):
    """4-bit window j of exact (20, TB) scalar digits; static j."""
    a, r = divmod(4 * j, BITS)
    v = d[a:a + 1] >> r
    if r > BITS - 4 and a + 1 < NL:
        v = v | (d[a + 1:a + 2] << (BITS - r))
    return v & 15


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _verify_core(pub, rb, k64, s32, fb_ymx_ref, fb_ypx_ref, fb_t2d_ref,
                 kw_ref, sw_ref):
    """Fused verify core: bytes → digits → sc_reduce64 → decompress(A)
    → R' = [S]B + [k](−A) → encode → compare against R. Inputs are raw
    byte rows: pub/rb (32, TB), the 64-byte sha512 output k (64, TB)
    and S (32, TB). y-canonicality (y<p), S canonicality and
    small-order rejection are checked on the jnp side (byte compares,
    cheap); everything else — including the digit conversions, the
    mod-l reduction of k and per-window scalar extraction — lives here
    in VMEM (r5: the jnp glue's bits-matmuls were ~1/3 of wall time).

    Pure jnp modulo _ROLL and the two (64, TB) window refs kw_ref/sw_ref
    (VMEM scratch in the kernel: Mosaic has no dynamic_slice of a
    value, so window j is read back from a ref at a dynamic row), so
    tests can run it bit-for-bit on CPU without Mosaic
    (tests/test_pallas_ed.py::test_verify_core_pure).

    Variable-base: per-lane 16-entry precomputed table of w·(−A), 64
    msb-first windows of 4 T-free doublings + 1 full doubling + 1 8-mul
    add. Fixed-base: doubling-free 7-mul mixed adds against the constant
    affine tables. Encode: one inversion chain + canonicalization; the
    final verdict is digit+sign equality with R (== canonical byte
    equality) ANDed with the decompression mask.
    """
    y = _bytes_to_digits(pub, NL, mask_top7=True)
    sign = pub[31:32] >> 7
    ry = _bytes_to_digits(rb, NL, mask_top7=True)
    rsign = rb[31:32] >> 7
    kd = _sc_reduce_rows(_bytes_to_digits(k64, 40), 40)
    sd = _bytes_to_digits(s32, NL)
    tb = y.shape[-1]
    one = pt_identity(tb)[1]

    # --- decompress A (RFC 8032 §5.1.3) ---
    y2 = fsq(y)
    u = fsub(y2, one)
    v = fadd(fmul_const(y2, fe.D_LIMBS), one)
    v3 = fmul(fsq(v), v)
    v7 = fmul(fsq(v3), v)
    x = fmul(fmul(u, v3), fpow_p58(fmul(u, v7)))
    vx2 = fmul(v, fsq(x))
    root_ok = fis_zero(fsub(vx2, u))
    root_neg = fis_zero(fadd(vx2, u))
    x = jnp.where(root_neg, fmul_const(x, fe.SQRT_M1_LIMBS), x)
    dec_ok = root_ok | root_neg
    xc = fcanon(x)
    x_is_zero = jnp.all(xc == 0, axis=0, keepdims=True)
    dec_ok = dec_ok & ~(x_is_zero & (sign == 1))
    flip = (xc[0:1] & 1) != sign
    ax = jnp.where(flip, fneg(x), x)
    ay = y
    at = fmul(ax, ay)

    # --- double scalar mul ---
    # −A (affine, z = 1)
    nx = fneg(ax)
    nt = fneg(at)
    a_neg_pre = (fsub(ay, nx), fadd(ay, nx), fmul_const(nt, fe.D2_LIMBS))

    # build 16-entry variable-base table in precomputed projective form
    full = [pt_identity(tb), (nx, ay, one, nt)]
    for _ in range(14):
        full.append(pt_madd_aff(full[-1], a_neg_pre))
    id_pre = (one, one, fmul_small2(one), jnp.zeros_like(one))
    vbtab = [id_pre] + [_to_pre(p) for p in full[1:]]

    # 4-bit windows of both scalars, materialized once (row shifts)
    kw_ref[...] = jnp.concatenate([_win4(kd, j) for j in range(64)], axis=0)
    sw_ref[...] = jnp.concatenate([_win4(sd, j) for j in range(64)], axis=0)

    def window_step(i, carry_pts):
        vacc, facc = carry_pts
        j = 63 - i
        # variable-base: 16·vacc + w_j·(−A)
        vacc = pt_dbl_not(vacc)
        vacc = pt_dbl_not(vacc)
        vacc = pt_dbl_not(vacc)
        vacc = pt_dbl_t(vacc)
        wk = kw_ref[pl.ds(j, 1), :]                   # (1, TB)
        vacc = pt_add_pre(vacc, _sel16(vbtab, wk))
        # fixed-base: += (w_j·16^j)·B
        ws = sw_ref[pl.ds(j, 1), :]
        ymx_j = fb_ymx_ref[j]                        # (16, NL)
        ypx_j = fb_ypx_ref[j]
        t2d_j = fb_t2d_ref[j]
        facc = pt_madd_aff(facc, _fb_entry(ymx_j, ypx_j, t2d_j, ws))
        return (vacc, facc)

    vacc, facc = jax.lax.fori_loop(
        0, 64, window_step, (pt_identity(tb), pt_identity(tb)))
    rpx, rpy, rpz, _ = pt_add_full(vacc, facc)

    # --- encode + compare with R in-kernel ---
    zinv = finv(rpz)
    xc2 = fcanon(fmul(rpx, zinv))
    yc = fcanon(fmul(rpy, zinv))
    match = jnp.all(yc == ry, axis=0, keepdims=True)
    match = match & ((xc2[0:1] & 1) == rsign)
    return (dec_ok & match).astype(jnp.int32)


def _verify_kernel(n_ref, pub_ref, r_ref, k64_ref, s32_ref,
                   fb_ymx_ref, fb_ypx_ref, fb_t2d_ref, ok_ref,
                   kw_ref, sw_ref):
    """Runs the core for a program that holds a lane below the live
    count n_ref[0]; a program made only of padding runs nothing and
    writes 0 (fail), so a dead lane never reads as verified."""
    live = pl.program_id(0) * ok_ref.shape[-1] < n_ref[0]

    @pl.when(live)
    def _run():
        ok_ref[:] = _verify_core(pub_ref[:], r_ref[:], k64_ref[:],
                                 s32_ref[:], fb_ymx_ref, fb_ypx_ref,
                                 fb_t2d_ref, kw_ref, sw_ref)

    @pl.when(jnp.logical_not(live))
    def _skip():
        ok_ref[:] = jnp.zeros(ok_ref.shape, jnp.int32)


# ---------------------------------------------------------------------------
# pallas_call wrappers
# ---------------------------------------------------------------------------

def _fe_spec(tb):
    return pl.BlockSpec((NL, tb), lambda i: (0, i), memory_space=pltpu.VMEM)


def _row_spec(tb):
    return pl.BlockSpec((1, tb), lambda i: (0, i), memory_space=pltpu.VMEM)


def _win_spec(tb):
    return pl.BlockSpec((64, tb), lambda i: (0, i), memory_space=pltpu.VMEM)


def _byte_spec(nrows, tb):
    def live_block(i, n_ref):
        # a program past the count maps its inputs to the last live
        # block: the pipeline skips a block it already holds
        return 0, jnp.minimum(i, jnp.maximum(n_ref[0] - 1, 0) // tb)
    return pl.BlockSpec((nrows, tb), live_block, memory_space=pltpu.VMEM)


def _tab_spec():
    return pl.BlockSpec((64, 16, NL), lambda i, n_ref: (0, 0, 0),
                        memory_space=pltpu.VMEM)


@functools.partial(jax.jit, static_argnames=("tb", "interpret"))  # fdlint: disable=missing-donate — inputs are host numpy (copied on transfer), nothing device-resident to donate
def verify_tpu(pub_t, r_t, k64_t, s32_t, n_live=None, tb=DEFAULT_TB,
               interpret=False):
    """Fused verify core. pub_t/r_t/s32_t (32, B) and k64_t (64, B)
    int32 LE byte rows (pub/R encodings, sha512(R||A||M) output, S).
    Returns ok (1, B).

    `n_live` (an int32 scalar, traced: one compiled program serves every
    count; None means all B) says lanes [0, n_live) are live. Only the
    grid programs that hold a live lane run; the lanes of the others
    read 0. A program's work per lane is the same either way."""
    b = pub_t.shape[-1]
    assert b % tb == 0, (b, tb)
    n = jnp.reshape(jnp.asarray(b if n_live is None else n_live,
                                jnp.int32), (1,))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b // tb,),
        in_specs=[_byte_spec(32, tb), _byte_spec(32, tb),
                  _byte_spec(64, tb), _byte_spec(32, tb),
                  _tab_spec(), _tab_spec(), _tab_spec()],
        out_specs=[pl.BlockSpec((1, tb), lambda i, n_ref: (0, i),
                                memory_space=pltpu.VMEM)],
        scratch_shapes=[pltpu.VMEM((64, tb), jnp.int32)] * 2)
    return pl.pallas_call(
        _verify_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((1, b), jnp.int32)],
        interpret=interpret,
    )(n, pub_t, r_t, k64_t, s32_t,
      *[jnp.asarray(t) for t in _fb_tables()])[0]


# ---------------------------------------------------------------------------
# glue: full verify with pallas core
# ---------------------------------------------------------------------------

def _pad_to(x, b_pad, axis=0):
    pad = b_pad - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _bytes_lt(b, const_int: int, mask_top7: bool = False):
    """(B, 32) u8 < const, LE lexicographic byte compare (no digit
    conversion — the glue's former bits-matmuls were the wall-time
    sink this replaces)."""
    c = const_int.to_bytes(32, "little")
    x = b.astype(jnp.int32)
    if mask_top7:
        x = jnp.concatenate([x[:, :31], x[:, 31:32] & 0x7F], axis=-1)
    lt = jnp.zeros(b.shape[:-1], bool)
    eq = jnp.ones(b.shape[:-1], bool)
    for i in range(31, -1, -1):
        ci = int(c[i])
        lt = lt | (eq & (x[:, i] < ci))
        eq = eq & (x[:, i] == ci)
    return lt


def verify_batch(sig, pub, msg, msg_len, n_live=None, tb=DEFAULT_TB,
                 interpret=False):
    """Drop-in equivalent of ops.ed25519.verify_batch on the Pallas path.

    sig (B, 64) u8, pub (B, 32) u8, msg (B, L) u8, msg_len (B,) i32
    -> (B,) bool. Batch is padded up to a multiple of `tb` internally.
    With `n_live`, lanes [0, n_live) are live: the kernel skips the
    `tb`-lane programs of padding (verify_tpu), and every lane past the
    count reads False.
    """
    bsz = sig.shape[0]
    b_pad = -(-bsz // tb) * tb

    r_bytes = sig[:, :32]
    s_bytes = sig[:, 32:]

    s_ok = _bytes_lt(s_bytes, ed.L)                      # S < l
    a_ok = _bytes_lt(pub, fe.P, mask_top7=True)          # y < p
    a_ok = a_ok & ~ed.is_small_order_encoding(pub)
    r_ok = ~ed.is_small_order_encoding(r_bytes)

    kmsg = jnp.concatenate([r_bytes, pub, msg], axis=-1)
    from .pallas_sha import sha512 as sha512_pl
    k64 = sha512_pl(kmsg, msg_len + 64, interpret=interpret)  # (B, 64)

    to_rows = lambda a: _pad_to(                          # noqa: E731
        jnp.moveaxis(a.astype(jnp.int32), 0, -1), b_pad, axis=1)
    ok = verify_tpu(to_rows(pub), to_rows(r_bytes), to_rows(k64),
                    to_rows(s_bytes), n_live=n_live, tb=tb,
                    interpret=interpret)
    ok = s_ok & a_ok & r_ok & (ok[0, :bsz] == 1)
    if n_live is not None:
        ok = ok & (jnp.arange(bsz) < n_live)
    return ok
