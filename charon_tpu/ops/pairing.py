"""Batched optimal-ate pairing on TPU: Miller loop + final exponentiation.

TPU-first design (vs the CPU oracle's textbook affine Fq12 loop,
crypto/pairing.py): the Miller loop runs on the M-twist with homogeneous
projective T — no inversions anywhere — and line values that are sparse Fq12
elements (coefficients in slots 1, v·w, v²·w). Lines are scaled by ξ·2y'·Z³
(resp. ξ·λ·Z³), all in the Fq2/Fq6 subfields, which the final exponentiation
kills. Derivation of the line shape:

  untwist ψ(x',y') = (x'/w², y'/w³);  w⁻¹ = v²w/ξ, w⁻³ = vw/ξ
  tangent at T, evaluated at P=(xp,yp) ∈ G1, scaled by ξ·2y'·Z³:
    l = 2YZ²·ξ·yp · 1 + (3X³ − 2Y²Z) · vw − 3X²Z·xp · v²w
  chord through T and affine Q=(xq,yq), scaled by ξ·λ·Z (θ = Y−yq·Z,
  λ = X−xq·Z):
    l = λ·ξ·yp · 1 + (θ·xq − λ·yq) · vw − θ·xp · v²w

The final exponentiation's hard part uses the Ghammam–Fouotsa addition chain
computing m^(3·(p⁴−p²+1)/r) — a fixed multiple coprime to r, so the
verification check `final_exp(f) == 1` is exact (validated against the CPU
oracle's naive exponentiation in tests).

The batch axis spans verification items (the reference's hot loop: per-partial
tbls.Verify in parsigex/validatorapi, reference core/parsigex/parsigex.go:61).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..crypto import curve as PC
from ..crypto import fields as PF
from ..utils import tracer
from . import buckets
from . import field as F
from . import tower as T
from .pallas_plane import TILE as _TILE

X_ABS = 0xD201000000010000
_X_BITS = bin(X_ABS)[3:]  # MSB implied; 63 steps, 5 additions


def _fq2_scale_fq(a, s):
    """Fq2 element × Fq scalar (both in Montgomery form)."""
    return jnp.stack([F.fq_mont_mul(a[..., 0, :], s),
                      F.fq_mont_mul(a[..., 1, :], s)], axis=-2)


def _dbl_step(f, Tp, xp, yp):
    """Doubling step: line through T,T evaluated at P; T <- 2T.
    Independent Fq2 products are staged into shared scans (see
    curve._fq2_mul_many)."""
    from .curve import _fq2_mul_many

    X, Y, Z = Tp
    XX, S, XY = _fq2_mul_many([(X, X), (Y, Z), (X, Y)])
    W = F.fq2_add(F.fq2_add(XX, XX), XX)         # 3X²
    SZ, XW, WZ, B_, WW, SS, YS = _fq2_mul_many(
        [(S, Z), (X, W), (W, Z), (XY, S), (W, W), (S, S), (Y, S)])
    # line: l = 2YZ²·ξ·yp + (3X³ − 2Y²Z)·vw − 3X²Z·xp·v²w
    a0 = T.fq2_mul_xi(_fq2_scale_fq(F.fq2_add(SZ, SZ), yp))
    avw = F.fq2_sub(XW, F.fq2_add(YS, YS))
    av2w = F.fq2_neg(_fq2_scale_fq(WZ, xp))
    # point update (homogeneous doubling, a=0)
    B4 = F.fq2_add(F.fq2_add(B_, B_), F.fq2_add(B_, B_))
    H = F.fq2_sub(WW, F.fq2_add(B4, B4))         # W² − 8B
    HS, WBH, Y2S2, S3 = _fq2_mul_many(
        [(H, S), (W, F.fq2_sub(B4, H)), (YS, YS), (SS, S)])
    X3 = F.fq2_add(HS, HS)
    Y2S2_8 = F.fq2_add(F.fq2_add(Y2S2, Y2S2), F.fq2_add(Y2S2, Y2S2))
    Y3 = F.fq2_sub(WBH, F.fq2_add(Y2S2_8, Y2S2_8))
    S3_4 = F.fq2_add(F.fq2_add(S3, S3), F.fq2_add(S3, S3))
    Z3 = F.fq2_add(S3_4, S3_4)                   # 8S³
    f = T.fq12_mul_sparse(f, a0, avw, av2w)
    return f, (X3, Y3, Z3)


def _add_step(f, Tp, xq, yq, xp, yp):
    """Mixed addition step: line through T and Q at P; T <- T + Q."""
    from .curve import _fq2_mul_many

    X, Y, Z = Tp
    yqZ, xqZ = _fq2_mul_many([(yq, Z), (xq, Z)])
    theta = F.fq2_sub(Y, yqZ)
    lam = F.fq2_sub(X, xqZ)
    ll, thth, th_xq, lam_yq = _fq2_mul_many(
        [(lam, lam), (theta, theta), (theta, xq), (lam, yq)])
    # line: l = λ·ξ·yp + (θ·xq − λ·yq)·vw − θ·xp·v²w
    a0 = T.fq2_mul_xi(_fq2_scale_fq(lam, yp))
    avw = F.fq2_sub(th_xq, lam_yq)
    av2w = F.fq2_neg(_fq2_scale_fq(theta, xp))
    lll, thZ, llA, llX = _fq2_mul_many(
        [(ll, lam), (thth, Z), (ll, F.fq2_add(X, xqZ)), (ll, X)])
    D = F.fq2_sub(thZ, llA)
    X3, thT, Ylll, Z3 = _fq2_mul_many(
        [(lam, D), (theta, F.fq2_sub(llX, D)), (Y, lll), (lll, Z)])
    Y3 = F.fq2_sub(thT, Ylll)
    f = T.fq12_mul_sparse(f, a0, avw, av2w)
    return f, (X3, Y3, Z3)


def _select_fq12(mask, a, b):
    def sel(x, y):
        return jnp.where(mask[..., None, None], x, y)
    return (tuple(sel(x, y) for x, y in zip(a[0], b[0])),
            tuple(sel(x, y) for x, y in zip(a[1], b[1])))


def _select_point(mask, p, q):
    return tuple(jnp.where(mask[..., None, None], x, y) for x, y in zip(p, q))


_X_BITS_ARR = jnp.asarray([int(b) for b in _X_BITS], dtype=jnp.int32)


def miller_loop_pairs(g1_points, g2_points):
    """Product of Miller loops over pair groups sharing one accumulator:
    f = Π_j f_{|x|,Q_j}(P_j), conjugated at the end (x < 0).

    Runs as a 63-step lax.scan; addition steps are computed every iteration
    and selected by the (static) bit pattern — uniform scan bodies beat a
    fully unrolled graph for XLA, at ~1.6× redundant point work.

    g1_points: list of (xp, yp) Fq arrays (batch, L).
    g2_points: list of (xq, yq) Fq2 arrays (batch, 2, L) on the twist.
    """
    f0 = T.fq12_one_like(g2_points[0][0])
    Ts0 = tuple((xq, yq, _one2_like(xq)) for (xq, yq) in g2_points)

    def step(state, bit):
        f, Ts = state
        f = T.fq12_sqr(f)
        Ts = list(Ts)
        for j, (xp, yp) in enumerate(g1_points):
            f, Ts[j] = _dbl_step(f, Ts[j], xp, yp)
        f_add = f
        Ts_add = list(Ts)
        for j, ((xp, yp), (xq, yq)) in enumerate(zip(g1_points, g2_points)):
            f_add, Ts_add[j] = _add_step(f_add, Ts_add[j], xq, yq, xp, yp)
        mask = jnp.broadcast_to(bit.astype(bool), f[0][0].shape[:-2])
        f = _select_fq12(mask, f_add, f)
        Ts = tuple(_select_point(mask, ta, t) for ta, t in zip(Ts_add, Ts))
        return (f, Ts), None

    (f, _), _ = jax.lax.scan(step, (f0, Ts0), _X_BITS_ARR)
    return T.fq12_conj(f)


def _one2_like(x):
    one = jnp.asarray(F.fq_from_int(1), dtype=jnp.int32)
    one = jnp.broadcast_to(one, x[..., 0, :].shape) + x[..., 0, :] * 0
    return jnp.stack([one, one * 0], axis=-2)


# The final exponentiation runs as a program over a small Fq12 register
# file: every step is R[dst] = map(R[a]) · R[b], map one of identity,
# conjugation, Frobenius¹, Frobenius², executed by ONE lax.scan whose body
# holds a single fq12_mul. Compile time follows graph size, and this keeps
# the graph to a few fq12_mul bodies whatever the chain's length; it also
# does one product per step where square-and-multiply did two per bit.
_ID, _CONJ, _FROB1, _FROB2 = range(4)
_R_F, _R_INV, _R_ONE, _R_M, _R_T0, _R_T1, _R_T2, _R_RES = range(8)
_N_REGS = 8


def _expt_steps(dst: int, src: int) -> list:
    """dst <- src^u for the (negative) BLS parameter u: conj(src^|u|),
    valid in the cyclotomic subgroup. Square-and-multiply from the implied
    leading one bit."""
    steps = [(dst, src, _ID, _R_ONE)]
    for bit in _X_BITS:
        steps.append((dst, dst, _ID, dst))
        if bit == "1":
            steps.append((dst, dst, _ID, src))
    return steps + [(dst, dst, _CONJ, _R_ONE)]


# easy part f^(p⁶−1)(p²+1), then the hard part ×3 (Ghammam–Fouotsa
# chain, validated vs the CPU oracle); comments name the chain's values.
_FINAL_EXP_TAPE = np.asarray(
    [(_R_M, _R_F, _CONJ, _R_INV),         # f1 = conj(f)·f⁻¹
     (_R_M, _R_M, _FROB2, _R_M),          # m = frob²(f1)·f1
     (_R_T0, _R_M, _ID, _R_M)]            # t0 = m²
    + _expt_steps(_R_T1, _R_M)            # t1 = m^u
    + [(_R_T1, _R_M, _CONJ, _R_T1)]       # t1 = conj(m)·t1
    + _expt_steps(_R_T2, _R_T1)           # t2 = t1^u
    + [(_R_T1, _R_T1, _CONJ, _R_T2)]      # t1 = conj(t1)·t2
    + _expt_steps(_R_T2, _R_T1)           # t2 = t1^u
    + [(_R_T1, _R_T1, _FROB1, _R_T2),     # t1 = frob(t1)·t2
       (_R_RES, _R_M, _ID, _R_T0)]        # res = m·t0
    + _expt_steps(_R_T0, _R_T1)           # t0 = t1^u
    + _expt_steps(_R_T2, _R_T0)           # t2 = t0^u
    + [(_R_T0, _R_T1, _FROB2, _R_ONE),    # t0 = frob²(t1)
       (_R_T1, _R_T1, _CONJ, _R_T2),      # t1 = conj(t1)·t2
       (_R_T1, _R_T1, _ID, _R_T0),        # t1 = t1·t0
       (_R_RES, _R_RES, _ID, _R_T1)],     # res = res·t1
    dtype=np.int32)

_MAPS = (lambda x: x, T.fq12_conj,
         lambda x: T.fq12_frobenius(x, 1), lambda x: T.fq12_frobenius(x, 2))


def _fq12_pack(f):
    return jnp.stack([*f[0], *f[1]])


def _fq12_unpack(r):
    return ((r[0], r[1], r[2]), (r[3], r[4], r[5]))


def final_exp_is_one(f):
    """final_exponentiation(f) == 1, computed as f^(3·(p¹²−1)/r) == 1.
    Since gcd(3, r) = 1 this is equivalent to the standard check."""
    pf = _fq12_pack(f)
    regs = jnp.stack([pf, _fq12_pack(T.fq12_inv(f)),
                      _fq12_pack(T.fq12_one_like(f[0][0]))]
                     + [pf * 0] * (_N_REGS - 3))

    def step(regs, ins):
        dst, a, op, b = ins[0], ins[1], ins[2], ins[3]
        x = jax.lax.switch(op, _MAPS, _fq12_unpack(regs[a]))
        prod = T.fq12_mul(x, _fq12_unpack(regs[b]))
        return regs.at[dst].set(_fq12_pack(prod)), None

    regs, _ = jax.lax.scan(step, regs, jnp.asarray(_FINAL_EXP_TAPE))
    return T.fq12_is_one(_fq12_unpack(regs[_R_RES]))


# ---------------------------------------------------------------------------
# Batched BLS verification kernel
# ---------------------------------------------------------------------------

# −G1 generator (host constant).
_G1_NEG = (PC.g1_generator()[0], PF.fq_neg(PC.g1_generator()[1]))


@functools.lru_cache(maxsize=8)
def _compiled_verify(batch: int):
    neg_g1_x = jnp.asarray(F.fq_from_int(_G1_NEG[0]))
    neg_g1_y = jnp.asarray(F.fq_from_int(_G1_NEG[1]))

    @jax.jit
    def verify_pairs(pk_x, pk_y, h_x, h_y, sig_x, sig_y):
        # e(pk, H(m))·e(−G1, sig) == 1  ⟺  e(pk, H(m)) == e(G1, sig)
        gx = jnp.broadcast_to(neg_g1_x, pk_x.shape)
        gy = jnp.broadcast_to(neg_g1_y, pk_y.shape)
        f = miller_loop_pairs([(pk_x, pk_y), (gx, gy)],
                              [(h_x, h_y), (sig_x, sig_y)])
        return final_exp_is_one(f)

    return verify_pairs


def _bucket(n: int) -> int:
    return buckets.pow2_bucket(n, floor=8)


def verify_batch_device(pubkeys_affine, h2c_affine, sigs_affine) -> np.ndarray:
    """Batched verification of k independent (pk, H(m), sig) triples.

    Inputs are host-side affine int coordinates:
      pubkeys_affine: list of (x, y) G1 ints
      h2c_affine:     list of ((x0,x1), (y0,y1)) G2 twist ints — hash points
      sigs_affine:    list of ((x0,x1), (y0,y1)) G2 twist ints
    Returns a bool array: per-item signature validity.
    """
    B = len(pubkeys_affine)
    if B == 0:
        return np.zeros(0, dtype=bool)
    Bp = _bucket(B)

    def pad(items, make):
        out = [make(v) for v in items]
        out += [out[0]] * (Bp - B)
        return np.stack(out)

    pk_x = pad(pubkeys_affine, lambda v: F.fq_from_int(v[0]))
    pk_y = pad(pubkeys_affine, lambda v: F.fq_from_int(v[1]))
    h_x = pad(h2c_affine, lambda v: F.fq2_from_ints(*v[0]))
    h_y = pad(h2c_affine, lambda v: F.fq2_from_ints(*v[1]))
    s_x = pad(sigs_affine, lambda v: F.fq2_from_ints(*v[0]))
    s_y = pad(sigs_affine, lambda v: F.fq2_from_ints(*v[1]))

    kernel = _compiled_verify(Bp)
    ok = kernel(jnp.asarray(pk_x), jnp.asarray(pk_y), jnp.asarray(h_x),
                jnp.asarray(h_y), jnp.asarray(s_x), jnp.asarray(s_y))
    return np.asarray(ok)[:B]


# ---------------------------------------------------------------------------
# RLC-folded multi-pairing check kernel (the production finish path)
# ---------------------------------------------------------------------------
#
# plane_agg._pairing_finish verifies one slot as Π e(Pᵢ, Qᵢ) == 1 over a
# handful of pairs (one per distinct message plus the (−g1, S) signature
# pair). The kernel runs every pair's Miller loop on its own batch lane,
# tree-folds the per-lane f values into one Fq12 product, and runs a
# SINGLE final exponentiation on the product — final_exp(Π fᵢ) == 1 is the
# multi-pairing check (conjugation for the negative parameter commutes
# with the product). Negations ride in the caller's G1 y-coordinates.


def _fq12_slice(f, a: int, b: int):
    return (tuple(c[a:b] for c in f[0]), tuple(c[a:b] for c in f[1]))


def _fq12_fold_product(f, batch: int):
    """Pairwise tree product over a power-of-two batch axis -> batch 1."""
    while batch > 1:
        half = batch // 2
        f = T.fq12_mul(_fq12_slice(f, 0, half), _fq12_slice(f, half, batch))
        batch = half
    return f


@functools.lru_cache(maxsize=8)
def _compiled_pairing_check(batch: int):
    @jax.jit
    def pairing_check(p_x, p_y, q_x, q_y, mask):
        f = miller_loop_pairs([(p_x, p_y)], [(q_x, q_y)])
        f = _select_fq12(mask, f, T.fq12_one_like(q_x))
        return final_exp_is_one(_fq12_fold_product(f, batch))

    return pairing_check


# Lane ceiling of one Miller-loop dispatch: one kernel tile. Pair sets
# beyond it run as successive ≤TILE chunk dispatches whose per-chunk Fq12
# products fold across chunks before the single final exponentiation —
# pairing multiplicativity (Π over chunks of Π within chunk == Π over all
# pairs) makes the chunked verdict bit-identical to a monolithic graph
# while the compiled shape family stays bounded at TILE lanes.
MAX_PAIR_TILE = _TILE


@functools.lru_cache(maxsize=8)
def _compiled_miller_fold(batch: int):
    """One chunk of the chunked multi-pairing check: per-lane Miller loops,
    masked to Fq12 one on padding lanes, tree-folded to a batch-1 Fq12
    product. No final exponentiation — that runs once, downstream, on the
    cross-chunk product (_compiled_chunk_finish)."""

    @jax.jit
    def miller_fold(p_x, p_y, q_x, q_y, mask):
        f = miller_loop_pairs([(p_x, p_y)], [(q_x, q_y)])
        f = _select_fq12(mask, f, T.fq12_one_like(q_x))
        return _fq12_fold_product(f, batch)

    return miller_fold


@functools.lru_cache(maxsize=8)
def _compiled_chunk_finish(k: int):
    """Cross-chunk finish: fold k per-chunk Fq12 products (each a batch
    lane of the six Fq2 coefficient arrays) and run the ONE final
    exponentiation. Padding lanes are masked to one."""

    @jax.jit
    def chunk_finish(c0, c1, c2, c3, c4, c5, mask):
        f = ((c0, c1, c2), (c3, c4, c5))
        f = _select_fq12(mask, f, T.fq12_one_like(c0))
        return final_exp_is_one(_fq12_fold_product(f, k))

    return chunk_finish


def _bucket_pairs(n: int) -> int:
    return buckets.pow2_bucket(n, floor=2)


def _fq12_concat(fs):
    """Concatenate per-chunk Fq12 products along the batch axis."""
    return (tuple(jnp.concatenate([f[0][i] for f in fs]) for i in range(3)),
            tuple(jnp.concatenate([f[1][i] for f in fs]) for i in range(3)))


def _pad_lane0(a, Bp: int, n: int):
    return buckets.pad_lane0(a, Bp, n)


def miller_fold_chunk(p_x, p_y, q_x, q_y):
    """Dispatch ONE ≤TILE chunk's Miller loops + in-graph fold; returns the
    chunk's batch-1 Fq12 product as device arrays (no sync — successive
    chunk dispatches queue behind each other asynchronously)."""
    m = p_x.shape[0]
    Bp = _bucket_pairs(m)
    mask = buckets.live_mask(m, Bp)
    kern = _compiled_miller_fold(Bp)
    return kern(jnp.asarray(_pad_lane0(np.asarray(p_x), Bp, m)),
                jnp.asarray(_pad_lane0(np.asarray(p_y), Bp, m)),
                jnp.asarray(_pad_lane0(np.asarray(q_x), Bp, m)),
                jnp.asarray(_pad_lane0(np.asarray(q_y), Bp, m)),
                jnp.asarray(mask))


def fold_chunks_is_one(parts) -> bool:
    """Fold a list of per-chunk Fq12 products (batch-1 each) through the
    pairwise tree and run the single final exponentiation."""
    k = len(parts)
    if k == 1:
        c0 = parts[0][0][0]
        mask = np.ones(c0.shape[0], dtype=bool)
        f = parts[0]
        ok = _compiled_chunk_finish(c0.shape[0])(
            *f[0], *f[1], jnp.asarray(mask))
        return _wait_verdict(ok, "chunk_finish")
    Kp = _bucket_pairs(k)
    f = _fq12_concat(parts)
    mask = buckets.live_mask(k, Kp)

    def padf(c):
        if Kp == k:
            return c
        return jnp.concatenate([c, jnp.repeat(c[:1], Kp - k, axis=0)])

    cs = [padf(c) for c in (*f[0], *f[1])]
    ok = _compiled_chunk_finish(Kp)(*cs, jnp.asarray(mask))
    return _wait_verdict(ok, "chunk_finish")


def _pairing_check_chunked(p_x, p_y, q_x, q_y) -> bool:
    """>TILE pair sets: successive TILE-lane Miller dispatches, each folded
    to one Fq12 on device, then one cross-chunk finish dispatch. Every
    compiled shape stays ≤ TILE lanes."""
    n = p_x.shape[0]
    arrs = tuple(np.asarray(a) for a in (p_x, p_y, q_x, q_y))
    parts = [miller_fold_chunk(*(a[lo:hi] for a in arrs))
             for lo, hi in buckets.chunk_spans(n, MAX_PAIR_TILE)]
    return fold_chunks_is_one(parts)


def pairing_check_planes(p_x, p_y, q_x, q_y) -> bool:
    """Π e(Pᵢ, Qᵢ) == 1 over Montgomery limb planes: p_* are (n, L) affine
    G1 coordinates, q_* are (n, 2, L) affine G2 twist coordinates, all
    non-infinity (degenerate pairs are the caller's host-side contract —
    see plane_agg._pairing_finish). Pads to the power-of-two bucket with
    masked repeats of lane 0; beyond MAX_PAIR_TILE pairs the check runs
    chunked (see _pairing_check_chunked) with a bit-identical verdict."""
    n = p_x.shape[0]
    if n == 0:
        return True
    if n > MAX_PAIR_TILE:
        return _pairing_check_chunked(p_x, p_y, q_x, q_y)
    Bp = _bucket_pairs(n)
    mask = buckets.live_mask(n, Bp)
    kernel = _compiled_pairing_check(Bp)
    ok = kernel(jnp.asarray(_pad_lane0(np.asarray(p_x), Bp, n)),
                jnp.asarray(_pad_lane0(np.asarray(p_y), Bp, n)),
                jnp.asarray(_pad_lane0(np.asarray(q_x), Bp, n)),
                jnp.asarray(_pad_lane0(np.asarray(q_y), Bp, n)),
                jnp.asarray(mask))
    return _wait_verdict(ok, "pairing_check")


def _wait_verdict(ok, program: str) -> bool:
    """Block on a check program's one-lane verdict: the host's wait for the
    chip, as an ops/device_wait span naming the jitted function."""
    with tracer.start_span("ops/device_wait", program=program):
        return bool(np.asarray(ok).reshape(-1)[0])


def warm_check_buckets(buckets=(2,)) -> int:
    """Ahead-of-time compile the bucketed multi-pairing check graphs into
    jax's (persistent) compile cache without executing them. Returns the
    number of graphs lowered."""
    L = F.LIMBS
    n = 0
    for b in buckets:
        fq = jax.ShapeDtypeStruct((b, L), jnp.int32)
        fq2 = jax.ShapeDtypeStruct((b, 2, L), jnp.int32)
        m = jax.ShapeDtypeStruct((b,), jnp.bool_)
        _compiled_pairing_check(b).lower(fq, fq, fq2, fq2, m).compile()
        n += 1
    return n


def warm_chunk_graphs(chunk_buckets=(MAX_PAIR_TILE,),
                      finish_buckets=(2, 4)) -> int:
    """AOT-compile the chunked-verify graph family: per-chunk Miller+fold
    at each chunk bucket, plus the cross-chunk finish at each chunk-count
    bucket. Returns the number of graphs lowered."""
    L = F.LIMBS
    n = 0
    for b in chunk_buckets:
        fq = jax.ShapeDtypeStruct((b, L), jnp.int32)
        fq2 = jax.ShapeDtypeStruct((b, 2, L), jnp.int32)
        m = jax.ShapeDtypeStruct((b,), jnp.bool_)
        _compiled_miller_fold(b).lower(fq, fq, fq2, fq2, m).compile()
        n += 1
    for k in finish_buckets:
        fq2 = jax.ShapeDtypeStruct((k, 2, L), jnp.int32)
        m = jax.ShapeDtypeStruct((k,), jnp.bool_)
        _compiled_chunk_finish(k).lower(*([fq2] * 6), m).compile()
        n += 1
    return n
