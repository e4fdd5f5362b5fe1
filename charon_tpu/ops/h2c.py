"""Device hash-to-curve for BLS12-381 G2 (RFC 9380 SSWU_RO_) on TPU.

Port of the host big-int reference (crypto/hash_to_curve.py) onto the
ops/field Montgomery limb plane. The split follows SURVEY §7: SHA-256
`expand_message_xmd` stays on host — bytes and hashing are host-shaped
work — producing Fq2 field elements shipped to device as limb planes;
the curve math (simplified SWU onto the 3-isogenous curve E', the
3-isogeny back to E, and the cofactor clear by the ψ endomorphism of
RFC 9380 Appendix G.3) runs branchlessly over the batch axis where
throughput comes from width.

Design notes:
  * Fq2 square roots use the complex method (valid because p ≡ 3 mod 4)
    with branchless candidate selection; squareness is the Euler test on
    the Fq norm — mirroring the host reference's `_is_square_fq2` /
    `fq2_sqrt` exactly, so outputs are bit-identical to the host path.
  * sgn0(u) ships from host (u is host-known); sgn0(y) is computed on
    device after a Montgomery→standard conversion (multiply by raw 1).
  * The 3-isogeny is evaluated inversion-free straight into Jacobian
    coordinates (Z = x_den·y_den); the single Fq2 inversion happens once
    at the end for the affine output the pairing kernel consumes.
  * Graphs are bucketed by padded batch like ops/pairing: powers of two
    capped at the plane TILE, so at most log2(TILE)+1 graph variants can
    ever compile (the persistent-cache bound app.assemble warms against).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..crypto import fields as PF
from ..crypto import hash_to_curve as HH
from ..crypto.curve import psi_consts
from ..utils import tracer
from . import buckets as BK
from . import field as F
from . import pallas_plane as PP
from . import tower as T
from .curve import FQ2_OPS, add_unified, double

DST_ETH = HH.DST_ETH

# Largest h2c batch a single dispatch takes — the same TILE that bounds the
# aggregation plane's chunk geometry, so the bucket family stays identical
# to the batch buckets the sigagg graphs already specialize on.
MAX_BATCH = PP.TILE


def _c2(v) -> np.ndarray:
    return np.asarray(F.fq2_from_ints(*v), dtype=np.int32)


# SSWU / isogeny constants as Montgomery limb planes (host-precomputed from
# the validated reference constants — see crypto/hash_to_curve.py docstring
# for how tests pin them).
_A = _c2(HH.A_ISO)
_B = _c2(HH.B_ISO)
_Z = _c2(HH.Z_SSWU)
_NEG_B_A = _c2(HH._NEG_B_OVER_A)
# exceptional-case x1 = B/(Z·A) (tv == 0 in the SSWU map)
_X1_EXC = _c2(PF.fq2_mul(HH.B_ISO, PF.fq2_inv(PF.fq2_mul(HH.Z_SSWU,
                                                         HH.A_ISO))))
_K1 = [_c2(c) for c in HH._K1]
_K2 = [_c2(c) for c in HH._K2] + [_c2(PF.FQ2_ONE)]  # monic x²
_K3 = [_c2(c) for c in HH._K3]
_K4 = [_c2(c) for c in HH._K4] + [_c2(PF.FQ2_ONE)]  # monic x³

_MONT_ONE = np.asarray(F.fq_from_int(1), dtype=np.int32)
# multiplying a Montgomery element by RAW 1 is the Montgomery→standard
# conversion (a·R · 1 · R⁻¹ = a) — how the device reads parity for sgn0
_RAW_ONE = np.asarray(F.limbs_from_int(1), dtype=np.int32)
_INV2 = np.asarray(F.fq_from_int((F.P_INT + 1) // 2), dtype=np.int32)


def _bits_arr(n: int) -> jnp.ndarray:
    return jnp.asarray([int(b) for b in bin(n)[2:]], dtype=jnp.int32)


_P14_BITS = _bits_arr((F.P_INT + 1) // 4)   # Fq sqrt exponent (p ≡ 3 mod 4)
_P12_BITS = _bits_arr((F.P_INT - 1) // 2)   # Euler QR test exponent


# ---------------------------------------------------------------------------
# Device field helpers
# ---------------------------------------------------------------------------


def _fq_pow_scan(a, bits):
    """a^k for a fixed exponent given as a static MSB-first bit array —
    the tower.fq_inv square-and-multiply scan generalized to any exponent."""
    one = jnp.broadcast_to(jnp.asarray(_MONT_ONE), a.shape) + a * 0

    def step(acc, bit):
        acc = F.fq_sqr(acc)
        mul = F.fq_mont_mul(acc, a)
        return jnp.where(bit.astype(bool), mul, acc), None

    acc, _ = jax.lax.scan(step, one, bits)
    return acc


def _fq_eq(a, b):
    return jnp.all(a == b, axis=-1)


def _fq2_eq(a, b):
    return jnp.all(a == b, axis=(-1, -2))


def _fq2_norm(a):
    a0, a1 = a[..., 0, :], a[..., 1, :]
    return F.fq_add(F.fq_sqr(a0), F.fq_sqr(a1))


def _fq2_is_square(a):
    """Euler criterion on the Fq norm (a square in Fq2 iff norm(a) is a
    square in Fq); zero counts as square, matching the host reference."""
    norm = _fq2_norm(a)
    e = _fq_pow_scan(norm, _P12_BITS)
    one = jnp.asarray(_MONT_ONE)
    return jnp.logical_or(F.fq_is_zero(norm), _fq_eq(e, one))


def _fq2_sqrt(a):
    """Branchless Fq2 square root via the complex method (p ≡ 3 mod 4).

    Callers only use this where a root exists (SSWU picks the square gx);
    the result is unspecified for non-squares. The a1 == 0 corner where
    a0 is a non-residue — sqrt = (0, sqrt(−a0)) — is covered by a second
    candidate selected when the complex-method candidate fails to square
    back to a."""
    a0, a1 = a[..., 0, :], a[..., 1, :]
    inv2 = jnp.asarray(_INV2)
    alpha = _fq_pow_scan(_fq2_norm(a), _P14_BITS)
    d1 = F.fq_mont_mul(F.fq_add(a0, alpha), inv2)
    x0a = _fq_pow_scan(d1, _P14_BITS)
    d2 = F.fq_mont_mul(F.fq_sub(a0, alpha), inv2)
    x0b = _fq_pow_scan(d2, _P14_BITS)
    x0 = F.fq_select(_fq_eq(F.fq_sqr(x0a), d1), x0a, x0b)
    x1c = F.fq_mont_mul(F.fq_mont_mul(a1, inv2), T.fq_inv(x0))
    cand = jnp.stack([x0, x1c], axis=-2)
    s_neg = _fq_pow_scan(F.fq_neg(a0), _P14_BITS)
    cand_b = jnp.stack([x0 * 0, s_neg], axis=-2)
    return F.fq2_select(_fq2_eq(F.fq2_sqr(cand), a), cand, cand_b)


def _sgn0(a):
    """RFC 9380 sgn0 for m = 2: parity of the standard-form coordinates,
    with the c1 parity taking over when c0 == 0."""
    a0s = F.fq_mont_mul(a[..., 0, :], jnp.asarray(_RAW_ONE))
    a1s = F.fq_mont_mul(a[..., 1, :], jnp.asarray(_RAW_ONE))
    sign0 = a0s[..., 0] & 1
    sign1 = a1s[..., 0] & 1
    zero0 = F.fq_is_zero(a0s).astype(jnp.int32)
    return sign0 | (zero0 & sign1)


# ---------------------------------------------------------------------------
# SSWU map, 3-isogeny, cofactor clear
# ---------------------------------------------------------------------------


def _sswu(u, u_sgn):
    """Simplified SWU: Fq2 limb element u -> affine point on E'. u_sgn is
    sgn0(u) computed on host (int32, batch-shaped)."""
    A, B, Z = jnp.asarray(_A), jnp.asarray(_B), jnp.asarray(_Z)
    u2 = F.fq2_sqr(u)
    zu2 = F.fq2_mul(Z, u2)
    tv = F.fq2_add(F.fq2_sqr(zu2), zu2)
    tv_zero = F.fq2_is_zero(tv)
    one2 = jnp.stack([jnp.asarray(_MONT_ONE), jnp.asarray(_MONT_ONE) * 0],
                     axis=-2) + u * 0
    # fq2_inv(0) = 0, so the tv == 0 lanes compute garbage that the select
    # below replaces with the exceptional-case constant B/(Z·A)
    x1 = F.fq2_mul(jnp.asarray(_NEG_B_A), F.fq2_add(one2, T.fq2_inv(tv)))
    x1 = F.fq2_select(tv_zero, jnp.broadcast_to(jnp.asarray(_X1_EXC),
                                                x1.shape), x1)
    gx1 = F.fq2_add(F.fq2_mul(F.fq2_add(F.fq2_sqr(x1), A), x1), B)
    x2 = F.fq2_mul(zu2, x1)
    gx2 = F.fq2_add(F.fq2_mul(F.fq2_add(F.fq2_sqr(x2), A), x2), B)
    sq1 = _fq2_is_square(gx1)
    x = F.fq2_select(sq1, x1, x2)
    gx = F.fq2_select(sq1, gx1, gx2)
    y = _fq2_sqrt(gx)
    flip = jnp.not_equal(u_sgn, _sgn0(y))
    y = F.fq2_select(flip, F.fq2_neg(y), y)
    return x, y


def _horner(coeffs, x):
    """Σ coeffs[i]·xⁱ (coeffs low→high, host constants) over device Fq2."""
    acc = jnp.broadcast_to(jnp.asarray(coeffs[-1]), x.shape) + x * 0
    for c in reversed(coeffs[:-1]):
        acc = F.fq2_add(F.fq2_mul(acc, x), jnp.asarray(c))
    return acc


def _iso_map(x, y):
    """3-isogeny E' -> E, inversion-free into Jacobian coordinates with
    Z = x_den·y_den (X/Z² = x_num/x_den, Y/Z³ = y·y_num/y_den)."""
    xn = _horner(_K1, x)
    xd = _horner(_K2, x)
    yn = _horner(_K3, x)
    yd = _horner(_K4, x)
    Zj = F.fq2_mul(xd, yd)
    yd2 = F.fq2_sqr(yd)
    Xj = F.fq2_mul(F.fq2_mul(xn, xd), yd2)
    xd2 = F.fq2_sqr(xd)
    Yj = F.fq2_mul(F.fq2_mul(F.fq2_mul(y, yn), F.fq2_mul(xd2, xd)), yd2)
    return (Xj, Yj, Zj)


# Cofactor clearing by RFC 9380 Appendix G.3 (Budroni–Pintore): on E2,
#   [h_eff]P = [x²−x−1]P + [x−1]ψ(P) + ψ²(2P),   x = −X_ABS,
# evaluated as the RFC's chain
#   t1 = [x]P;  t2 = ψ(P) + t1;  t3 = [x]t2;  Q = ψ²(2P) + t3 − t2 − P.
# It runs as a program over a small register file of points: every step is
# R[dst] = op(map(R[a]), R[b]), map one of identity, negation, ψ, ψ², op one
# of doubling (R[b] unused) and complete addition, executed by ONE lax.scan
# whose switches run only the taken branch. Each [x]· is [|x|] of the
# negated point, double-and-add from the implied leading bit: 63 doublings
# and 5 additions, since |x| has Hamming weight 6.
_ID, _NEG, _PSI, _PSI2 = range(4)
_DBL, _ADD = range(2)
_R_P, _R_T1, _R_T2, _R_T3, _R_Q = range(5)
_N_PT_REGS = 5


def _mul_x_steps(dst: int, src: int) -> list:
    """dst <- [x]src = [|x|](−src), MSB-first from the implied leading one."""
    steps = []
    for i, bit in enumerate(bin(PF.X_ABS)[3:]):
        steps.append((dst, src, _NEG, _DBL, dst) if i == 0
                     else (dst, dst, _ID, _DBL, dst))
        if bit == "1":
            steps.append((dst, src, _NEG, _ADD, dst))
    return steps


_COFACTOR_TAPE = np.asarray(
    _mul_x_steps(_R_T1, _R_P)                  # t1 = [x]P
    + [(_R_T2, _R_P, _PSI, _ADD, _R_T1)]       # t2 = ψ(P) + t1
    + _mul_x_steps(_R_T3, _R_T2)               # t3 = [x]t2
    + [(_R_Q, _R_P, _PSI2, _DBL, _R_Q),        # Q = ψ²(2P) = 2ψ²(P)
       (_R_Q, _R_T3, _ID, _ADD, _R_Q),         # Q = Q + t3
       (_R_Q, _R_T2, _NEG, _ADD, _R_Q),        # Q = Q − t2
       (_R_Q, _R_P, _NEG, _ADD, _R_Q)],        # Q = Q − P
    dtype=np.int32)

_PSI_CX, _PSI_CY = (_c2(c) for c in psi_consts())


def _conj(a):
    """Fq2 conjugation (the Frobenius): negate the c1 limb plane."""
    return jnp.stack([a[..., 0, :], F.fq_neg(a[..., 1, :])], axis=-2)


def _psi(p):
    X, Y, Z = p
    psiX, psiY = FQ2_OPS.mul_many([(_conj(X), jnp.asarray(_PSI_CX)),
                                   (_conj(Y), jnp.asarray(_PSI_CY))])
    return (psiX, psiY, _conj(Z))


_MAPS = (lambda p: p,
         lambda p: (p[0], F.fq2_neg(p[1]), p[2]),
         _psi,
         lambda p: _psi(_psi(p)))
_OPS = (lambda p, q: double(FQ2_OPS, p),
        lambda p, q: add_unified(FQ2_OPS, p, q))


def _clear_cofactor(p):
    """[h_eff]·P for Jacobian points P on E2 (any batch shape), by the
    G.3 tape above."""
    pp = jnp.stack(p)
    regs = jnp.stack([pp] + [pp * 0] * (_N_PT_REGS - 1))

    def step(regs, ins):
        dst, a, m, op, b = ins[0], ins[1], ins[2], ins[3], ins[4]
        x = jax.lax.switch(m, _MAPS, tuple(regs[a]))
        r = jax.lax.switch(op, _OPS, x, tuple(regs[b]))
        return regs.at[dst].set(jnp.stack(r)), None

    regs, _ = jax.lax.scan(step, regs, jnp.asarray(_COFACTOR_TAPE))
    return tuple(regs[_R_Q])


@functools.lru_cache(maxsize=8)
def _compiled_h2c(batch: int):
    """The bucketed map-to-G2 graph: (u0, u1, sgn0 pair) limb planes ->
    affine (x, y) limb planes of the G2 hash point."""

    @jax.jit
    def map_to_g2(u0, u1, s0, s1):
        # both field elements go through ONE map on a doubled batch: half
        # the graph (and the compile) of mapping them one after the other
        q = _iso_map(*_sswu(jnp.concatenate([u0, u1]),
                            jnp.concatenate([s0, s1])))
        q0 = tuple(c[:batch] for c in q)
        q1 = tuple(c[batch:] for c in q)
        r = _clear_cofactor(add_unified(FQ2_OPS, q0, q1))
        zi = T.fq2_inv(r[2])
        zi2 = F.fq2_sqr(zi)
        hx = F.fq2_mul(r[0], zi2)
        hy = F.fq2_mul(r[1], F.fq2_mul(zi2, zi))
        return hx, hy

    return map_to_g2


# ---------------------------------------------------------------------------
# Host entry points
# ---------------------------------------------------------------------------


def _bucket(n: int) -> int:
    return BK.pow2_bucket(n, floor=1)


def hash_to_field_planes(msgs, dst: bytes = DST_ETH):
    """Host half: expand_message_xmd + hash_to_field per message, shipped
    as Montgomery limb planes (u0, u1: (B, 2, L)) plus the host-computed
    sgn0 of each u ((B,) int32 each)."""
    u0s, u1s, s0s, s1s = [], [], [], []
    for m in msgs:
        u0, u1 = HH.hash_to_field_fq2(bytes(m), dst, 2)
        u0s.append(F.fq2_from_ints(*u0))
        u1s.append(F.fq2_from_ints(*u1))
        s0s.append(HH._sgn0_fq2(u0))
        s1s.append(HH._sgn0_fq2(u1))
    return (np.stack(u0s).astype(np.int32), np.stack(u1s).astype(np.int32),
            np.asarray(s0s, dtype=np.int32), np.asarray(s1s, dtype=np.int32))


def map_to_g2_device(u0, u1, s0, s1):
    """Device half over pre-built limb planes: pad to the power-of-two
    bucket (≤ MAX_BATCH) and run the bucketed graph. Returns device arrays
    — callers choose when to sync."""
    B = u0.shape[0]
    Bp = min(_bucket(B), MAX_BATCH)
    if B > MAX_BATCH:
        raise ValueError(f"h2c batch {B} exceeds MAX_BATCH={MAX_BATCH}")

    kernel = _compiled_h2c(Bp)
    hx, hy = kernel(jnp.asarray(BK.pad_lane0(u0, Bp, B)),
                    jnp.asarray(BK.pad_lane0(u1, Bp, B)),
                    jnp.asarray(BK.pad_lane0(s0, Bp, B)),
                    jnp.asarray(BK.pad_lane0(s1, Bp, B)))
    return hx, hy


def hash_to_g2_device(msgs, dst: bytes = DST_ETH):
    """Full hash_to_curve for a message batch on device: returns affine
    (hx, hy) numpy limb planes of shape (B, 2, L), bit-identical to the
    host reference crypto.hash_to_curve.hash_to_g2 (RFC 9380 vectors and
    the host oracle pin this in tests). Batches beyond MAX_BATCH run as
    successive TILE-sized dispatches, so the graph bucket family stays
    bounded."""
    B = len(msgs)
    if B == 0:
        L = F.LIMBS
        return (np.zeros((0, 2, L), np.int32), np.zeros((0, 2, L), np.int32))
    outs = []
    for lo, hi in BK.chunk_spans(B, MAX_BATCH):
        chunk = msgs[lo:hi]
        u0, u1, s0, s1 = hash_to_field_planes(chunk, dst)
        hx, hy = map_to_g2_device(u0, u1, s0, s1)
        with tracer.start_span("ops/device_wait", program="map_to_g2"):
            outs.append((np.asarray(hx)[:len(chunk)],
                         np.asarray(hy)[:len(chunk)]))
    return (np.concatenate([o[0] for o in outs]),
            np.concatenate([o[1] for o in outs]))


def warm_buckets(buckets=(1,)) -> int:
    """Ahead-of-time compile the bucketed h2c graphs into jax's (persistent)
    compile cache without executing them. Returns the number of graphs
    lowered. Callers gate on the device-verify path being enabled."""
    L = F.LIMBS
    n = 0
    for b in buckets:
        if b > MAX_BATCH:
            continue
        fq2 = jax.ShapeDtypeStruct((b, 2, L), jnp.int32)
        s = jax.ShapeDtypeStruct((b,), jnp.int32)
        _compiled_h2c(b).lower(fq2, fq2, s, s).compile()
        n += 1
    return n
