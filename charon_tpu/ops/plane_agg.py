"""Product-level TPU crypto dispatches built on the fused Pallas kernels.

Two hot paths from the duty pipeline (reference hot loops: per-partial
tbls.Verify in core/parsigex/parsigex.go:61 and core/validatorapi, and
per-validator tbls.ThresholdAggregate + aggregate Verify in
core/sigagg/sigagg.go:144,159):

threshold_aggregate_batch — per-validator Lagrange combination Σ λⱼ·sigⱼ for
a whole batch of validators in one device sweep. The T partial signatures
of each validator live in T lane-blocks of one batch, so the 4-bit-windowed
scalar sweep runs once over T·V points; the per-validator combine is then
log₂T unified adds. Outputs are bit-identical to the CPU oracle (both
compute Σ λⱼ·sigⱼ exactly, same ETH serialization).

threshold_aggregate_and_verify — the fused sigagg hot path: the RLC
verification consumes the freshly computed aggregate plane, with the MSMs
dispatched asynchronously so the device affine serialization overlaps them.

rlc_verify_batch — random-linear-combination batch verification (the same
trick as blst's mult-verify): sample RLC_BITS-bit rᵢ, compute S = Σ rᵢ·sigᵢ
(G2 MSM, on device) and per distinct message P_m = Σ rᵢ·pkᵢ (G1 MSM, on
device), then check Π e(P_m, H(m)) · e(−g1, S) == 1 with one native
multi-pairing (ct_pairing_check). Soundness: a forged batch passes with
probability ≤ 2^-RLC_BITS over the rᵢ (see RLC_BITS below). On failure the
caller falls back to per-item verification for attribution.

Host⇄device traffic is kept lean: on a real device the decompression
square roots, Montgomery conversion, subgroup checks, and affine output
conversion all run batched on device (the native C++ bulk decode remains
the small-batch/interpret path and the test oracle); host work is byte
slicing plus uint8 digit-plane uploads.
"""

from __future__ import annotations

import contextvars
import ctypes
import functools
import threading
from collections import OrderedDict, deque
from concurrent.futures import Future, InvalidStateError, ThreadPoolExecutor
from concurrent.futures import TimeoutError as _FuturesTimeout
from concurrent.futures import wait as _futures_wait

import jax
import jax.numpy as jnp
import numpy as np

from ..crypto import fields as PF
from ..utils import faults, metrics, tracer
from ..crypto.curve import (g1_generator, jac_add, jac_is_infinity, FqOps,
                            Fq2Ops, psi_consts)
from ..crypto.rlc import RLC_BITS, sample_randomizers
from ..crypto.serialize import g1_to_bytes, g2_to_bytes
from . import field as F
from . import pallas_plane as PP
from . import policy as policy_mod
from . import sentinel

_MONT_ONE = F.fq_from_int(1)

# Dispatch-phase latency split of the fused sigagg slot: "pack" is host
# parse + async dispatch (_fused_dispatch), "execute" is the host's wait on
# the explicit block_until_ready fence (not device time: a graph that
# finished while the host packed the next slot waits ~0), "drain" is the
# readback transfer after the fence, "finish" is the pure-host back half
# (emit bytes + RLC folds, _fused_host_finish) and "verify" is the slot's
# RLC-folded pairing check (_pairing_finish — one batched device dispatch
# of h2c + multi-Miller-loop + final-exp on the device path, the ctypes
# native rung behind the guard otherwise; bulk verifies observe it too).
# finish/verify are the stages the pipeline overlaps on its worker
# executor. Buckets run past the 12 s slot: a verify that hashes a new
# root to the curve takes ~7.5 s on one v5e.
_dispatch_hist = metrics.histogram(
    "ops_device_dispatch_seconds",
    "Fused sigagg dispatch phases: host pack, host wait on the device "
    "fence (execute), drain-side readback transfer, host finish, pairing "
    "verify", ("phase",),
    buckets=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
             0.5, 1, 2.5, 5, 10, 15, 30))

# Stage-3 (host finish) slots scheduled on the pipeline executor but not
# yet completed — a persistently high value means the finish stage is the
# pipeline bound (widen FINISH_WORKERS or profile the finish phase).
_finish_backlog = metrics.gauge(
    "ops_sigagg_finish_backlog",
    "SigAggPipeline slots whose stage-3 host finish has not completed")

# Slots whose emit half is done but whose verify dispatch (the deferred
# back half of stage 3) has not completed — a persistently high value
# means verification, not byte emission, is the stage-3 bound.
_verify_backlog = metrics.gauge(
    "ops_sigagg_verify_backlog",
    "SigAggPipeline slots whose deferred verify phase has not completed")

# Shard width of the most recent sigagg dispatch: 1 on the single-device
# path, the mesh width on the sharded path. Health cross-checks this
# against ops_mesh_devices — a mesh wider than the dispatched width means
# slots are not being promoted onto the sharded plane.
_shard_width = metrics.gauge(
    "ops_sigagg_shard_width",
    "Devices the current sigagg slot's validator axis is sharded over "
    "(PER-HOST width on a multi-host mesh)")

# Per-host twin of ops_sigagg_shard_width, labelled by host index: on a
# multi-host mesh every host sets its own row, so a scrape across the
# cluster shows which host narrowed its rung after a device loss (the
# guard ladder narrows per-host). Single-host nodes show one row, host="0".
_host_shard_width = metrics.gauge(
    "ops_sigagg_host_shard_width",
    "Per-host devices the current sigagg slot's validator axis is sharded "
    "over, labelled by mesh host index", ("host",))

# Whole slots queued in the pipeline (dispatched, finish not yet consumed)
# — the serving layer's backpressure signal: core/coalesce estimates drain
# time from its own in-flight count, and this gauge is the device-plane
# ground truth an operator correlates a 503 shed against.
_submit_backlog = metrics.gauge(
    "ops_sigagg_submit_backlog",
    "SigAggPipeline slots in flight (submitted, result not yet consumed)")


@functools.lru_cache(maxsize=4096)
def _lagrange(ids: tuple[int, ...]) -> tuple[int, ...]:
    return tuple(PF.lagrange_coefficients_at_zero(list(ids)))


def _bucket(n: int) -> int:
    """Batch -> plane bucket; shares pad_batch's sub-tile buckets so a
    small slot's plane (and with it the whole fused graph) shrinks with
    the batch instead of flooring at one full 1024 tile."""
    return PP.pad_batch(n)


def _bucket_for_slots(V: int, T: int) -> int:
    """Per-validator bucket whose T-slot combined plane (Vp·T elements)
    is ITSELF a valid padded size — the permuted slot layout addresses the
    combined plane directly, so its width must land exactly on a bucket."""
    step = min(PP.TILE, PP.MIN_TILE)
    Vp = _bucket(V)
    while PP.pad_batch(Vp * T) != Vp * T:
        Vp += step
    return Vp


def _native_lib():
    from ..tbls.native_impl import load_library

    return load_library()


def _device_path(n: int = 1 << 30) -> bool:
    """Whether the batched DEVICE decoders/serializer should run (vs the
    native bulk path). On a real chip: yes for non-trivial batches. In
    interpret mode the native path is the default, but tests force this
    True to exercise the full device pipeline on the CPU CI mesh
    (tests/test_plane_agg_interp.py) — the exact code the driver benches
    must never be green-in-CI yet crash-at-bench."""
    return not PP._interpret() and n >= 64


# Verification pairs fed to each multi-pairing path: "device" is the
# batched TPU Miller loop + final exponentiation (ops/pairing), "native"
# the ctypes ct_pairing_check rung behind the guard — the same
# path-attribution shape as dkg_msm_total.
_pairing_c = metrics.counter(
    "ops_pairing_total",
    "Multi-pairing verification pairs by execution path: device = batched "
    "TPU Miller loop + final exp, native = ctypes ct_pairing_check (guard "
    "fallback rung / hosts without an accelerator)", ("path",))

def _verify_device_path() -> bool:
    """Whether _pairing_finish runs the slot verification on device.
    CHARON_TPU_DEVICE_VERIFY=0/1 forces it off/on (tests, triage);
    otherwise it is ON — interpret mode included. There is no pair-count
    ceiling anymore: >TILE pair sets run as chunked ≤TILE Miller
    dispatches folded before one final exp (pairing.MAX_PAIR_TILE), and
    the breaker + native rung stay underneath as the safety net. CPU CI
    sets CHARON_TPU_DEVICE_VERIFY=0 in tests/conftest.py because the
    pairing graph costs minutes of XLA:CPU compile — the exact hazard
    tests/test_device_pairing.py slow-gates. Resolved through the
    SlotPolicy seam (installed policy → env → on)."""
    return policy_mod.device_verify_default()


# ---------------------------------------------------------------------------
# Bulk compressed-bytes -> kernel-plane loaders
# ---------------------------------------------------------------------------


def _fp_limbs_raw(be: np.ndarray) -> np.ndarray:
    """(N, 48) big-endian Fp byte strings -> (N, 32) int32 RAW 12-bit limbs
    (standard form, NOT Montgomery). Fully numpy-vectorized — the Montgomery
    conversion happens on device via one multiply by R² (see
    _to_mont_on_device), so no per-value Python bigints touch the hot path."""
    n = be.shape[0]
    le = be[:, ::-1]  # little-endian
    b = le.reshape(n, 16, 3).astype(np.int32)
    lo = b[:, :, 0] | ((b[:, :, 1] & 0xF) << 8)
    hi = (b[:, :, 1] >> 4) | (b[:, :, 2] << 4)
    out = np.empty((n, 32), np.int32)
    out[:, 0::2] = lo
    out[:, 1::2] = hi
    return out


@functools.lru_cache(maxsize=16)
def _r2_plane(S: int, W: int):
    """Broadcast plane of the plain value R² mod p: mont_mul(x_raw, R²) =
    x·R mod p, i.e. the Montgomery conversion. Cached as NUMPY (a jnp array
    built inside a jit trace would be a tracer — caching it leaks it across
    traces)."""
    col = np.asarray(F.limbs_from_int(F.R2_INT), np.int32)
    return np.broadcast_to(
        col[None, :, None, None], (1, F.LIMBS, S, W)).copy()


def _to_mont_on_device(plane, E: int):
    """Per-Fq-component Montgomery conversion of an (E, LIMBS, 8, W) plane
    of raw standard-form limbs. E components are packed onto the lane axis
    (pallas_plane's _pack/_unpack convention) so the multiply is a single
    plain-Fq CIOS pass (NO Fq2 cross terms)."""
    S, W = plane.shape[-2:]
    packed = PP._pack(plane)
    r2 = _r2_plane(S, packed.shape[-1])
    out = PP._mul_call(packed[None], r2, 1)[0]
    return PP._unpack(out, E)


def g2_plane_from_compressed(sigs: list[bytes], Bp: int,
                             check_subgroup: bool = False,
                             reject_infinity: bool = False) -> PP.PlanePoint:
    """Compressed G2 points -> kernel plane (affine Z=1; ∞ and padding get
    Z=0). Raises ValueError on a point that fails curve decoding (and, when
    requested, subgroup membership) or on a disallowed infinity.

    On a real device the decompression square roots run batched on device
    (_g2_plane_device); the native bulk decode remains the interpret-mode /
    small-batch path and the oracle the device decoder is tested against."""
    n = len(sigs)
    if _device_path(n):
        plane = _g2_plane_device(sigs, Bp, reject_infinity)
        if check_subgroup and not g2_subgroup_ok(plane):
            raise ValueError("G2 point not in subgroup")
        return plane
    lib = _native_lib()
    out = (ctypes.c_uint8 * (192 * n))()
    rc = lib.ct_g2_uncompress_bulk(b"".join(bytes(s) for s in sigs), n, out,
                                   1 if check_subgroup else 0)
    if rc != n:
        raise ValueError(f"invalid G2 point at index {-rc - 1}")
    aff = np.frombuffer(bytes(out), np.uint8).reshape(n, 4, 48)
    inf = ~np.any(aff.reshape(n, -1), axis=1)
    if reject_infinity and inf.any():
        raise ValueError("infinity G2 point rejected")
    limbs = _fp_limbs_raw(aff.reshape(n * 4, 48)).reshape(n, 4, 32)
    X = np.zeros((Bp, 2, F.LIMBS), np.int32)
    Y = np.zeros_like(X)
    Z = np.zeros_like(X)
    X[:n, 0], X[:n, 1] = limbs[:, 0], limbs[:, 1]
    Y[:n, 0], Y[:n, 1] = limbs[:, 2], limbs[:, 3]
    Z[:n, 0] = np.where(inf[:, None], 0, _MONT_ONE[None, :])

    Xp = _to_mont_on_device(jnp.asarray(PP.to_plane(X, 2)), 2)
    Yp = _to_mont_on_device(jnp.asarray(PP.to_plane(Y, 2)), 2)
    Zp = jnp.asarray(PP.to_plane(Z, 2))  # mont(1)/0 constant, already mont
    return PP.PlanePoint(Xp, Yp, Zp, 2, Bp)


def g1_plane_from_compressed(pks: list[bytes], Bp: int,
                             check_subgroup: bool = False,
                             reject_infinity: bool = False,
                             device_decode: bool | None = None) -> PP.PlanePoint:
    n = len(pks)
    if device_decode is None:
        device_decode = _device_path(n)
    if device_decode:
        plane = _g1_plane_device(pks, Bp, reject_infinity)
        if check_subgroup and not g1_subgroup_ok(plane):
            raise ValueError("G1 point not in subgroup")
        return plane
    lib = _native_lib()
    out = (ctypes.c_uint8 * (96 * n))()
    rc = lib.ct_g1_uncompress_bulk(b"".join(bytes(s) for s in pks), n, out,
                                   1 if check_subgroup else 0)
    if rc != n:
        raise ValueError(f"invalid G1 point at index {-rc - 1}")
    aff = np.frombuffer(bytes(out), np.uint8).reshape(n, 2, 48)
    inf = ~np.any(aff.reshape(n, -1), axis=1)
    if reject_infinity and inf.any():
        raise ValueError("infinity G1 point rejected")
    limbs = _fp_limbs_raw(aff.reshape(n * 2, 48)).reshape(n, 2, 32)
    X = np.zeros((Bp, F.LIMBS), np.int32)
    Y = np.zeros_like(X)
    Z = np.zeros_like(X)
    X[:n] = limbs[:, 0]
    Y[:n] = limbs[:, 1]
    Z[:n] = np.where(inf[:, None], 0, _MONT_ONE[None, :])

    Xp = _to_mont_on_device(jnp.asarray(PP.to_plane(X, 1)), 1)
    Yp = _to_mont_on_device(jnp.asarray(PP.to_plane(Y, 1)), 1)
    Zp = jnp.asarray(PP.to_plane(Z, 1))
    return PP.PlanePoint(Xp, Yp, Zp, 1, Bp)


# ---------------------------------------------------------------------------
# Device decompression: the per-point square root dominated the single host
# core (native Fq2 sqrt ≈ 250µs/point; 4000 partials ≈ 1s). Here the sqrt
# runs BATCHED on device as fixed-exponent power chains (blind
# square-and-multiply scans over the whole plane), with only byte slicing
# and flag parsing left on the host. Bit-compatible with the native/Python
# decoders (serialize.py g{1,2}_from_bytes): same flag rules, x < p gate,
# lexicographic y-sign convention, and off-curve rejection (sqrt failure).
# ---------------------------------------------------------------------------

_EXP_SQRT = None  # (p+1)/4 window digits, lazily built
_EXP_INV = None   # p-2 window digits
_EXP_34 = None    # (p-3)/4 window digits
# The tables depend on POW_WINDOW, which enable_compile_lean may still flip
# at startup, so they must stay lazy — and the first decode can arrive from
# the event loop, a verify worker, and a watchdog recovery at once.
_exp_lock = threading.Lock()


def _sqrt_inv_bits():
    global _EXP_SQRT, _EXP_INV
    if _EXP_SQRT is None:
        with _exp_lock:
            if _EXP_SQRT is None:
                # _EXP_INV first: an unlocked reader that sees _EXP_SQRT
                # non-None must also see _EXP_INV populated
                _EXP_INV = PP.exp_digits(PF.P - 2)
                _EXP_SQRT = PP.exp_digits((PF.P + 1) // 4)
    return _EXP_SQRT, _EXP_INV


def _e34_bits():
    """(p−3)/4 window digits: a^((p-3)/4) gives root = s·a and, for a QR,
    1/root = root·s² in the same scan (p ≡ 3 mod 4)."""
    global _EXP_34
    if _EXP_34 is None:
        with _exp_lock:
            if _EXP_34 is None:
                _EXP_34 = PP.exp_digits((PF.P - 3) // 4)
    return _EXP_34


_P_BE = np.frombuffer(PF.P.to_bytes(48, "big"), np.uint8).astype(np.int16)


def _lex_lt_p(be48: np.ndarray) -> np.ndarray:
    """(n, 48) big-endian byte rows -> (n,) bool: value < p."""
    diff = be48.astype(np.int16) - _P_BE[None]
    nz = diff != 0
    anynz = nz.any(axis=1)
    first = diff[np.arange(len(be48)), np.argmax(nz, axis=1)]
    return anynz & (first < 0)


_HALF_LIMBS = None


@functools.lru_cache(maxsize=16)
def _one_raw_plane(S: int, W: int):
    """Broadcast plane of the RAW value 1: mont_mul(x_mont, 1) = x·R·R⁻¹ =
    x, i.e. the Montgomery→standard conversion. Cached as NUMPY (see
    _r2_plane)."""
    col = np.zeros(F.LIMBS, np.int32)
    col[0] = 1
    return np.broadcast_to(
        col[None, :, None, None], (1, F.LIMBS, S, W)).copy()


def _gt_half_std(plane):
    """(1, LIMBS, 8, W) STANDARD-form canonical Fq plane -> (8, W) bool:
    value > (p-1)/2 (the lexicographic y-sign threshold)."""
    global _HALF_LIMBS
    if _HALF_LIMBS is None:
        with _exp_lock:
            if _HALF_LIMBS is None:
                _HALF_LIMBS = [int(v)
                               for v in F.limbs_from_int((PF.P - 1) // 2)]
    x = plane[0]
    gt = jnp.zeros(x.shape[-2:], bool)
    eq = jnp.ones(x.shape[-2:], bool)
    for j in reversed(range(F.LIMBS)):
        gt = gt | (eq & (x[j] > _HALF_LIMBS[j]))
        eq = eq & (x[j] == _HALF_LIMBS[j])
    return gt


def _gt_half(plane):
    """Montgomery-form variant of _gt_half_std: converts to standard form
    first — limb comparison on Montgomery residues would be meaningless."""
    S, W = plane.shape[-2:]
    return _gt_half_std(PP._mul_call(plane, _one_raw_plane(S, W), 1))


def _raw_to_plane(be48: np.ndarray, Bp: int) -> "np.ndarray":
    """(n, 48) BE bytes -> (1, LIMBS, 8, W) raw-limb plane (standard form)."""
    limbs = _fp_limbs_raw(be48)
    arr = np.zeros((Bp, F.LIMBS), np.int32)
    arr[:len(be48)] = limbs
    return PP.to_plane(arr, 1)


def _fq_sqrt_device(a):
    """Batched Fq sqrt candidate on a packed plane: s = a^((p+1)/4) and the
    validity mask s² == a (p ≡ 3 mod 4). Zero maps to zero (valid)."""

    sqrt_bits, _ = _sqrt_inv_bits()
    s = PP._pow_scan(a, jnp.asarray(sqrt_bits))
    s2 = PP._mul_call(s, s, 1)
    ok = jnp.all(s2 == a, axis=(0, 1))
    return s, ok


def _parse_compressed(items: list[bytes], size: int, kind: str,
                      reject_infinity: bool, Bp: int):
    """Shared host-side byte parsing/validation for the device decoders.
    Returns (body, fin, sgn_padded, lmask_rows) with serialize.py's flag
    rules enforced (compression bit, infinity encoding, x < p)."""
    n = len(items)
    data = np.frombuffer(b"".join(bytes(s) for s in items),
                         np.uint8).reshape(n, size)
    flags = data[:, 0]
    if not (flags & 0x80).all():
        raise ValueError(f"uncompressed {kind} not supported")
    inf = (flags & 0x40) != 0
    sign = ((flags & 0x20) >> 5).astype(np.int32)
    body = data.copy()
    body[:, 0] &= 0x1F
    if inf.any():
        if reject_infinity:
            raise ValueError(f"infinity {kind} point rejected")
        bad = inf & (body.any(axis=1) | (sign == 1))
        if bad.any():
            raise ValueError(
                f"invalid {kind} point at index {int(np.argmax(bad))}")
    fin = ~inf
    for off in range(0, size, 48):
        if not _lex_lt_p(body[fin, off:off + 48]).all():
            raise ValueError(f"invalid {kind} point: x not in field")
    sgn = np.zeros(Bp, np.int32)
    sgn[:n] = sign
    loaded = np.zeros(Bp, bool)
    loaded[:n] = fin
    W = Bp // PP.SUB
    return body, fin, sgn.reshape(PP.SUB, W), loaded.reshape(PP.SUB, W)


def _raise_bad(okm: np.ndarray, kind: str) -> None:
    raise ValueError(
        f"invalid {kind} point at index {int(np.argmax(~okm.reshape(-1)))}")


@jax.jit
def _g1_decompress_jit(Xr, splane, lmask):
    """Raw-limb x plane + sign/loaded masks -> (X, Y, Z, okmask), all in ONE
    compiled dispatch (eager per-op dispatches would each pay a host↔device
    round trip)."""
    return _g1_decompress_core(Xr, splane, lmask)


def _g1_decompress_core(Xr, splane, lmask):
    from ..crypto.curve import B_G1

    X = _to_mont_on_device(Xr, 1)
    S, W = X.shape[-2:]
    xsq = PP._mul_call(X, X, 1)
    xcube = PP._mul_call(xsq, X, 1)
    y2 = PP.fe_add(xcube, _const_plane((B_G1,), 1, S, W), 1)
    y, ok = _fq_sqrt_device(y2)
    flip = (_gt_half(y).astype(jnp.int32) != splane) & lmask
    Y = jnp.where(flip[None, None], PP.fe_neg(y, 1), y)
    Y = jnp.where(lmask[None, None], Y, 0)
    X = jnp.where(lmask[None, None], X, 0)
    Z = jnp.where(lmask[None, None],
                  _const_plane((1,), 1, S, W), 0)  # mont(1) where loaded
    return X, Y, Z, ok | ~lmask


def _g1_plane_device(pks: list[bytes], Bp: int,
                     reject_infinity: bool) -> PP.PlanePoint:

    body, fin, sgn, loaded = _parse_compressed(
        pks, 48, "G1", reject_infinity, Bp)
    Xr = jnp.asarray(_raw_to_plane(body, Bp))
    X, Y, Z, ok = _g1_decompress_jit(Xr, jnp.asarray(sgn),
                                     jnp.asarray(loaded))
    with tracer.start_span("ops/device_wait", program="_g1_decompress_jit"):
        okm = np.asarray(ok)
    if not okm.all():
        _raise_bad(okm, "G1")
    return PP.PlanePoint(X, Y, Z, 1, Bp)


@jax.jit
def _g2_decompress_jit(X0r, X1r, splane, lmask):
    """Raw-limb x component planes + sign/loaded masks -> (X, Y, Z, okmask)
    in ONE compiled dispatch. The Fq2 square root follows fields.fq2_sqrt's
    complex method, branchless over the plane: alpha = sqrt(c0² + c1²),
    delta± = (c0 ± alpha)/2, y0 = sqrt(delta), y1 = c1/(2·y0), with the
    fallback candidate (0, sqrt(−c0)) for c1 == 0. sqrt runs as a blind
    square-and-multiply scan by the fixed exponent (p−3)/4: s = a^((p-3)/4)
    yields BOTH the root candidate y0 = s·a and, when a is a QR (s²·a = 1),
    the inverse 1/y0 = y0·s² — so the separate 1/y0 inversion scan of the
    naive method disappears (two scans per decompression, not three)."""
    return _g2_decompress_core(X0r, X1r, splane, lmask)


def _g2_decompress_core(X0r, X1r, splane, lmask):
    from ..crypto.curve import B_G2

    X0 = _to_mont_on_device(X0r, 1)
    X1 = _to_mont_on_device(X1r, 1)
    S, W = X0.shape[-2:]

    X = jnp.stack([X0[0], X1[0]], axis=0)
    Xsq = PP.fe_mul(X, X, 2)
    Xcb = PP.fe_mul(Xsq, X, 2)
    y2 = PP.fe_add(Xcb, _const_plane(B_G2, 2, S, W), 2)
    c0, c1 = y2[0][None], y2[1][None]

    norm = PP.fe_add(PP._mul_call(c0, c0, 1), PP._mul_call(c1, c1, 1), 1)
    alpha, _ = _fq_sqrt_device(norm)
    inv2 = _const_plane(((PF.P + 1) // 2,), 1, S, W)
    delta_p = PP._mul_call(PP.fe_add(c0, alpha, 1), inv2, 1)
    delta_m = PP._mul_call(PP.fe_sub(c0, alpha, 1), inv2, 1)
    neg_c0 = PP.fe_neg(c0, 1)
    packed = jnp.concatenate([delta_p, delta_m, neg_c0], axis=-1)
    # ONE (p−3)/4 scan serves all three candidates: root = s·a and, for the
    # QR that gets selected, 1/root = root·s² (s²·a == 1) — no separate
    # inversion scan (see _g2_decompress_jit docstring)
    s34 = PP._pow_scan(packed, jnp.asarray(_e34_bits()))
    roots = PP._mul_call(s34, packed, 1)
    x0p, x0m, s2c = (roots[..., :W], roots[..., W:2 * W], roots[..., 2 * W:])
    s_p, s_m = s34[..., :W], s34[..., W:2 * W]
    okp = jnp.all(PP._mul_call(x0p, x0p, 1) == delta_p, axis=(0, 1))
    y0 = jnp.where(okp[None, None], x0p, x0m)
    s_sel = jnp.where(okp[None, None], s_p, s_m)
    y0inv = PP._mul_call(y0, PP._mul_call(s_sel, s_sel, 1), 1)
    y1 = PP._mul_call(PP._mul_call(c1, inv2, 1), y0inv, 1)

    # validity: candidate (y0, y1)² == (c0, c1), else fallback (0, s2c)
    m0 = PP._mul_call(PP.fe_add(y0, y1, 1), PP.fe_sub(y0, y1, 1), 1)
    m1 = PP._mul_call(y0, y1, 1)
    valid1 = (jnp.all(m0 == c0, axis=(0, 1)) &
              jnp.all(PP.fe_add(m1, m1, 1) == c1, axis=(0, 1)))
    s2sq = PP._mul_call(s2c, s2c, 1)
    c1zero = jnp.all(c1 == 0, axis=(0, 1))
    valid2 = jnp.all(PP.fe_neg(s2sq, 1) == c0, axis=(0, 1)) & c1zero
    Y0 = jnp.where(valid1[None, None], y0, 0)
    Y1 = jnp.where(valid1[None, None], y1, s2c)
    ok = valid1 | valid2

    # lexicographic Fq2 sign: c1 != 0 ? c1 > half : c0 > half
    y1nz = ~jnp.all(Y1 == 0, axis=(0, 1))
    csign = jnp.where(y1nz, _gt_half(Y1), _gt_half(Y0)).astype(jnp.int32)
    flip = (csign != splane) & lmask
    Y0 = jnp.where(flip[None, None], PP.fe_neg(Y0, 1), Y0)
    Y1 = jnp.where(flip[None, None], PP.fe_neg(Y1, 1), Y1)

    Xp = jnp.where(lmask[None, None], X, 0)
    Yp = jnp.stack([jnp.where(lmask[None, None], Y0, 0)[0],
                    jnp.where(lmask[None, None], Y1, 0)[0]], axis=0)
    z0 = jnp.where(lmask[None, None], _const_plane((1,), 1, S, W), 0)
    Z = jnp.concatenate([z0, z0 * 0], axis=0)
    return Xp, Yp, Z, ok | ~lmask


def _g2_plane_device(sigs: list[bytes], Bp: int,
                     reject_infinity: bool) -> PP.PlanePoint:

    body, fin, sgn, loaded = _parse_compressed(
        sigs, 96, "G2", reject_infinity, Bp)
    X0r = jnp.asarray(_raw_to_plane(body[:, 48:], Bp))
    X1r = jnp.asarray(_raw_to_plane(body[:, :48], Bp))
    X, Y, Z, ok = _g2_decompress_jit(X0r, X1r, jnp.asarray(sgn),
                                     jnp.asarray(loaded))
    okm = np.asarray(ok)
    if not okm.all():
        _raise_bad(okm, "G2")
    return PP.PlanePoint(X, Y, Z, 2, Bp)


# ---------------------------------------------------------------------------
# Device subgroup checks (batched endomorphism tests)
#
# The per-point scalar-multiplication subgroup checks are the expensive CPU
# part of untrusted-input validation (native g{1,2}_in_subgroup does a 64/128
# bit scalar mul per point on the single host core). Here the same
# endomorphism rules run batched on the device:
#   G2:  psi(P) == [x]P   (x = -X_ABS; psi = untwist-Frobenius-twist)
#   G1:  phi(P) == [s·u²]P  (phi = (beta·x, y), beta a cube root of unity)
# The shared scalar u has Hamming weight 6, so [u]P is 63 fused doubles + 5
# adds over the whole batch. Infinity (and lane padding, Z=0) passes, like
# the native checks.
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=1)
def _g1_endo_consts():
    """(beta, sign) with phi(P) = (beta·x, y) == [sign·u²]P on G1 — found by
    the same search the native constant generator uses (native/gen_constants.py)."""
    from ..crypto.curve import FqOps, jac_mul, to_affine, to_jacobian

    g1 = g1_generator()
    aff = to_affine(FqOps, g1)
    for g in (2, 3, 5, 7):
        beta = pow(g, (PF.P - 1) // 3, PF.P)
        if beta == 1:
            continue
        phi = to_jacobian(FqOps, (aff[0] * beta % PF.P, aff[1]))
        for sign in (1, -1):
            tgt = jac_mul(FqOps, g1, (sign * PF.X_ABS * PF.X_ABS) % PF.R)
            if to_affine(FqOps, phi) == to_affine(FqOps, tgt):
                return beta, sign
    raise AssertionError("no beta/sign works for the G1 endomorphism")


@functools.lru_cache(maxsize=16)
def _const_plane(vals: tuple, E: int, S: int, W: int):
    """Broadcast Montgomery-form constant plane for fe_mul. Cached as NUMPY
    (see _r2_plane)."""
    if E == 1:
        col = F.fq_from_int(vals[0])[None]
    else:
        col = F.fq2_from_ints(*vals)
    return np.broadcast_to(
        col[:, :, None, None], (E, F.LIMBS, S, W)).copy()


def _jac_eq_mask(p: PP.PlanePoint, q: PP.PlanePoint):
    """(8, W) bool: per-element Jacobian equality (cross-multiplied affine
    comparison; ∞ == ∞, ∞ != finite)."""

    E = p.E
    z1z1 = PP.fe_mul(p.Z, p.Z, E)
    z2z2 = PP.fe_mul(q.Z, q.Z, E)
    lx = PP.fe_mul(p.X, z2z2, E)
    rx = PP.fe_mul(q.X, z1z1, E)
    z1c = PP.fe_mul(z1z1, p.Z, E)
    z2c = PP.fe_mul(z2z2, q.Z, E)
    ly = PP.fe_mul(p.Y, z2c, E)
    ry = PP.fe_mul(q.Y, z1c, E)
    eq = jnp.all((lx == rx) & (ly == ry), axis=(0, 1))
    inf1 = jnp.all(p.Z == 0, axis=(0, 1))
    inf2 = jnp.all(q.Z == 0, axis=(0, 1))
    return jnp.where(inf1 | inf2, inf1 & inf2, eq)


@jax.jit
def _g2_subgroup_jit(X, Y, Z):
    return _g2_subgroup_core(X, Y, Z)


def _g2_subgroup_core(X, Y, Z):
    S, W = X.shape[-2:]
    cx, cy = psi_consts()
    B = X.shape[-2] * X.shape[-1]
    # psi: conjugate each coord (component-wise negate of c1), scale X and Y
    psiX = PP.fe_mul(_conj_plane(X), _const_plane(cx, 2, S, W), 2)
    psiY = PP.fe_mul(_conj_plane(Y), _const_plane(cy, 2, S, W), 2)
    psi = PP.PlanePoint(psiX, psiY, _conj_plane(Z), 2, B)
    uX, uY, uZ = PP._shared_mul_call(X, Y, Z, PF.X_ABS, 2)
    xP = PP.PlanePoint(uX, PP.fe_neg(uY, 2), uZ, 2, B)  # [x]P = -[u]P
    return _jac_eq_mask(psi, xP).all()


def g2_subgroup_ok(p: PP.PlanePoint) -> bool:
    """True iff EVERY loaded element lies in the r-subgroup (padding/∞ pass).
    Matches native g2_in_subgroup (psi(P) == [x]P, bls12381.cpp:800); runs
    as one compiled dispatch."""
    ok = _g2_subgroup_jit(p.X, p.Y, p.Z)
    with tracer.start_span("ops/device_wait", program="_g2_subgroup_jit"):
        return bool(ok)


@jax.jit
def _g1_subgroup_jit(X, Y, Z):
    return _g1_subgroup_core(X, Y, Z)


def _g1_subgroup_core(X, Y, Z):
    S, W = X.shape[-2:]
    beta, sign = _g1_endo_consts()
    B = S * W
    phiX = PP.fe_mul(X, _const_plane((beta,), 1, S, W), 1)
    phi = PP.PlanePoint(phiX, Y, Z, 1, B)
    uX, uY, uZ = PP._shared_mul_call(X, Y, Z, PF.X_ABS * PF.X_ABS, 1)
    if sign < 0:
        uY = PP.fe_neg(uY, 1)
    u2P = PP.PlanePoint(uX, uY, uZ, 1, B)
    return _jac_eq_mask(phi, u2P).all()


def g1_subgroup_ok(p: PP.PlanePoint) -> bool:
    """True iff every loaded element lies in the r-subgroup; matches native
    g1_in_subgroup (phi(P) == [s·u²]P, bls12381.cpp:814); one dispatch."""
    ok = _g1_subgroup_jit(p.X, p.Y, p.Z)
    with tracer.start_span("ops/device_wait", program="_g1_subgroup_jit"):
        return bool(ok)


def _conj_plane(a):
    """Fq2 conjugate of a (2, LIMBS, 8, W) plane: negate the c1 component."""

    neg = PP.fe_neg(a, 2)
    return jnp.stack([a[0], neg[1]], axis=0)


# ---------------------------------------------------------------------------
# Threshold aggregation
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(4, 5))
def _sweep_combine_jit(X, Y, Z, digits_u8, T, Wv):
    """Windowed Lagrange sweep + per-validator combine (pairwise-add of the
    T lane blocks, log₂T rounds) as ONE compiled dispatch. digits_u8:
    (64, 8, W) uint8 window digits (4× leaner transfer than bit planes)."""
    return _sweep_combine_core(X, Y, Z, digits_u8, T, Wv)


def _sweep_combine_core(X, Y, Z, digits_u8, T, Wv):
    pX, pY, pZ = PP._scalar_mul_windowed(
        X, Y, Z, digits_u8.astype(jnp.int32), 2)
    parts = [(pX[..., j * Wv:(j + 1) * Wv], pY[..., j * Wv:(j + 1) * Wv],
              pZ[..., j * Wv:(j + 1) * Wv]) for j in range(T)]
    while len(parts) > 1:
        nxt = []
        for k in range(0, len(parts) - 1, 2):
            nxt.append(PP._add_call(*parts[k], *parts[k + 1], 2))
        if len(parts) % 2:
            nxt.append(parts[-1])
        parts = nxt
    return parts[0]


def _layout_slots(batches: list[dict[int, bytes]], Vp: int | None = None,
                  T: int | None = None):
    """Permuted slot layout for ONE combined load of all T·Vp points (a
    single device decompression dispatch instead of T): slot j lands on the
    lane block [j·Wv, (j+1)·Wv) of every sublane — the same layout the
    per-slot concatenate produced, so the combine slices lanes unchanged.

    Vp/T may be forced (the sharded plane lays out per-device chunks with
    globally-fixed plane dimensions); by default they derive from batches."""
    V = len(batches)
    if T is None:
        T = max(len(b) for b in batches)
    if T == 0:
        raise ValueError("empty partial signature set")
    if Vp is None:
        Vp = _bucket_for_slots(V, T)
    zero96 = b"\xc0" + bytes(95)  # compressed infinity

    Wv = Vp // PP.SUB
    W4 = (Vp * T) // PP.SUB
    sigs_all = [zero96] * (Vp * T)
    scalars_all = [0] * (Vp * T)
    for i, batch in enumerate(batches):
        ids = sorted(batch)
        lam = _lagrange(tuple(ids))
        base = (i // Wv) * W4 + (i % Wv)
        for j in range(len(ids)):
            flat = base + j * Wv
            sigs_all[flat] = bytes(batch[ids[j]])
            scalars_all[flat] = lam[j]
    return sigs_all, scalars_all, V, Vp, T, Wv


def _aggregate_plane(batches: list[dict[int, bytes]], layout=None):
    """Common front half of the aggregation paths: combined permuted load +
    windowed Lagrange sweep + per-validator combine. Returns the aggregate
    Jacobian plane (RX, RY, RZ) holding V results in a Vp-element plane."""
    sigs_all, scalars_all, V, Vp, T, Wv = layout or _layout_slots(batches)
    plane = g2_plane_from_compressed(sigs_all, Vp * T)
    digits = PP.scalars_to_digitplanes(scalars_all, Vp * T)
    RX, RY, RZ = _sweep_combine_jit(
        plane.X, plane.Y, plane.Z, jnp.asarray(digits), T, Wv)
    return RX, RY, RZ, V, Vp


def _serialize_aggregates(RX, RY, RZ, V: int) -> list[bytes]:
    if _device_path():
        # affine conversion + standard form on device; host only slices
        # bytes (the per-point host fq2 inversions/muls were ~0.4s/1000)
        return _g2_serialize_device(RX, RY, RZ, V)
    RX, RY, RZ = (np.asarray(c) for c in (RX, RY, RZ))
    flatX = PP.from_plane(RX, V)
    flatY = PP.from_plane(RY, V)
    flatZ = PP.from_plane(RZ, V)
    jacs = [(F.fq2_to_ints(flatX[i]), F.fq2_to_ints(flatY[i]),
             F.fq2_to_ints(flatZ[i])) for i in range(V)]
    return _g2_jacs_to_bytes(jacs)


def threshold_aggregate_batch(batches: list[dict[int, bytes]]) -> list[bytes]:
    """Aggregate many validators' threshold partial signatures in one device
    sweep. batches[i] maps share_idx -> 96-byte compressed G2 signature.
    Returns compressed aggregates, bit-identical to the CPU oracle."""
    if not batches:
        return []
    RX, RY, RZ, V, _ = _aggregate_plane(batches)
    return _serialize_aggregates(RX, RY, RZ, V)


def threshold_aggregate_and_verify(batches: list[dict[int, bytes]],
                                   pks: list[bytes], msgs: list[bytes],
                                   hash_fn=None):
    """Fused sigagg hot path: aggregate + RLC-verify in one device pass
    (reference sigagg aggregates then verifies the SAME signatures,
    core/sigagg/sigagg.go:144,159). The verification consumes the freshly
    computed aggregate PLANE directly — no serialize→re-decompress round
    trip, and no per-aggregate subgroup check (aggregates of in-subgroup
    partials stay in the subgroup; partials are subgroup-checked on receipt
    by parsigex/validatorapi, matching the reference's trust boundary).

    On a device this is ONE jitted dispatch + ONE blocking transfer
    (_fused_slot_jit); every extra host↔device sync adds to the slot's
    latency. Returns (compressed aggregates, all_valid)."""
    if not batches:
        return [], True
    if not (len(batches) == len(pks) == len(msgs)):
        raise ValueError("length mismatch")
    layout = _layout_slots(batches)
    sigs_all, scalars_all, V, Vp, T, Wv = layout
    if not _device_path(len(sigs_all)):
        RX, RY, RZ, V, Vp = _aggregate_plane(batches, layout)
        sig_plane = PP.PlanePoint(RX, RY, RZ, 2, Vp)
        try:
            pk_plane = _pk_plane_cached(pks, Vp)
        except ValueError:
            return _serialize_aggregates(RX, RY, RZ, V), False
        # dispatch the MSM device work FIRST, serialize while it runs —
        # the serialization's host loop overlaps the queued dispatches
        state = _rlc_dispatch(sig_plane, pk_plane, msgs)
        out = _serialize_aggregates(RX, RY, RZ, V)
        return out, _rlc_finish(state, hash_fn)

    from . import guard

    state = _dispatch_slot(batches, pks, msgs)
    return guard.finish_slot(state, (batches, pks, msgs), hash_fn)


def _sigagg_mesh():
    """The production mesh seam (ops/mesh.py): a >1-device Mesh routes
    device-path slots onto the sharded plane, None keeps the exact
    single-device path."""
    from . import mesh as mesh_mod

    return mesh_mod.sigagg_mesh()


def _dispatch_slot(batches, pks, msgs):
    """Stage-1 router for SigAggPipeline: sharded pack+dispatch across the
    mesh when ops.mesh reports >1 device, the single-device fused dispatch
    otherwise. Both sides are pure host-work + enqueue (no device sync),
    so the pipeline lock may cover this call (LINT-TPU-007).

    Guard integration (docs/robustness.md): when the plane circuit
    breaker is open the slot never touches the device — the "native_slot"
    tag sends guard.finish_slot straight to the bit-identical CPU rung.
    Device-class dispatch failures are *captured* as "dispatch_failed"
    (not raised) so the fallback ladder runs at finish time, OFF this
    lock; deterministic input errors still raise to the submitter."""
    from . import guard

    if not guard.allow_device_dispatch():
        return ("native_slot",)
    try:
        with sentinel.region("slot"):
            m = _sigagg_mesh()
            if m is not None:
                from . import sharded_plane

                return sharded_plane.sharded_dispatch(batches, pks, msgs, m)
            return _fused_dispatch(_layout_slots(batches), pks, msgs)
    except Exception as exc:
        if guard.classify(exc) == "input":
            raise
        return ("dispatch_failed", exc)


def _fused_dispatch(layout, pks, msgs):
    """Host parse + async device dispatch of one fused slot; returns the
    pending state for _fused_finish. Callers overlap the NEXT slot's host
    parse with this slot's device execution (the jax dispatch is async —
    nothing blocks until _fused_finish's device_get). The whole body is the
    "pack" phase of ops_device_dispatch_seconds: everything here is host
    work + enqueue."""
    with tracer.start_span("ops/fused_dispatch",
                           validators=layout[2]) as span, \
            _dispatch_hist.observe_time("pack"):
        state = _fused_dispatch_impl(layout, pks, msgs)
        span.attrs["outcome"] = state[0]
        _shard_width.set(1.0)
        from . import mesh as mesh_mod

        _host_shard_width.set(1.0, str(mesh_mod.host_index()))
        return state


def _fused_dispatch_impl(layout, pks, msgs):
    faults.check("sigagg.pack")
    sigs_all, scalars_all, V, Vp, T, Wv = layout
    body, _fin, sgn, loaded = _parse_compressed(
        sigs_all, 96, "G2", False, Vp * T)
    X0r = jnp.asarray(_raw_to_plane(body[:, 48:], Vp * T))
    X1r = jnp.asarray(_raw_to_plane(body[:, :48], Vp * T))
    ldigits = jnp.asarray(PP.scalars_to_digitplanes(scalars_all, Vp * T))
    try:
        pk_plane = _pk_plane_cached(pks, Vp)  # device; sync on miss only
    except ValueError:
        return ("bad_pk", layout)
    rdig = jnp.asarray(PP.scalars_to_digitplanes(
        sample_randomizers(V), Vp, nbits=RLC_BITS))
    group_msgs, gmask = _group_masks(msgs, V, Vp)
    outs = _fused_slot_jit(
        X0r, X1r, jnp.asarray(sgn), jnp.asarray(loaded), ldigits, rdig,
        pk_plane.X, pk_plane.Y, pk_plane.Z, jnp.asarray(gmask),
        T=T, Wv=Wv, G=len(group_msgs))
    return ("pending", V, group_msgs, outs)


def _fused_finish(state, hash_fn=None):
    """Complete one fused slot: device fence + readback (_fused_readback),
    then the pure-host back half (_fused_host_finish). This is the stable
    blocking seam — the guard ladder's rungs and the serial
    threshold_aggregate_and_verify path both come through here, so the
    "ops/fused_finish" span and the bad_pk degradation contract live at
    this level. The pipeline's stage-3 workers instead ride _fused_emit
    + the returned verify thunk, so slot N's verify dispatch overlaps
    slot N+1's pack — same verdicts, same phases, split seam."""
    out, verify = _fused_emit(state, hash_fn)
    return out, verify()


def _fused_emit(state, hash_fn=None):
    """The emit half of a slot's completion: device fence + readback +
    validity check + byte emission + RLC host folds. Returns
    (aggregates, verify_thunk); calling the thunk runs the slot's pairing
    verification (the separately-timed "verify" phase) and returns the
    verdict. Deferring the thunk is what lets the pipeline overlap slot
    N's verify with slot N+1's pack and the in-flight execute."""
    with tracer.start_span("ops/fused_finish") as span:
        return _fused_host_emit(_fused_readback(state, span), hash_fn)


def _fused_readback(state, span=None):
    """Stage 2→3 boundary: block on the slot's device work and transfer the
    results to host memory. An explicit jax.block_until_ready fence is the
    "execute" phase (pure device wait — on a pipelined caller this is where
    overlap shows up as ~0); the jax.device_get transfer alone is "drain".
    Returns the host-side state for _fused_host_finish ("bad_pk" states
    pass through untouched — there is no device work to wait for).
    Sharded-plane states (tag "sharded*") delegate to
    sharded_plane.sharded_readback — same phases, per-shard drain."""
    faults.check("sigagg.execute")
    if state[0].startswith("sharded"):
        from . import sharded_plane

        return sharded_plane.sharded_readback(state, span)
    if state[0] == "bad_pk":
        if span is not None:
            span.attrs["outcome"] = "bad_pk"
        return state
    _tag, V, group_msgs, outs = state
    with _dispatch_hist.observe_time("execute"):
        jax.block_until_ready(outs)
    if span is not None:
        span.add_event("device_fence")
    faults.check("sigagg.readback")
    with _dispatch_hist.observe_time("drain"):
        host = jax.device_get(outs)
    return ("host", V, group_msgs, host)


def _fused_host_finish(hstate, hash_fn=None):
    """Stage 3, blocking shape: emit half + immediate verify (see
    _fused_host_emit). Kept for callers that want the whole finish on one
    thread (guard ladder rungs, serial paths)."""
    out, verify = _fused_host_emit(hstate, hash_fn)
    return out, verify()


def _fused_host_emit(hstate, hash_fn=None):
    """Stage 3, emit half — validity check, bulk byte emission and RLC
    host folds (the "finish" phase of ops_device_dispatch_seconds).
    Returns (aggregates, verify_thunk): the thunk runs the slot's pairing
    verification (the separately-timed "verify" phase: chunked batched
    device dispatches, native ctypes rung behind the guard) when called.
    The heavy parts of both halves release the GIL, so the pipeline runs
    them as chained worker tasks overlapping the next slot's pack and the
    in-flight device execute."""
    faults.check("sigagg.finish")
    if hstate[0].startswith("sharded"):
        from . import sharded_plane

        return sharded_plane.sharded_host_emit(hstate, hash_fn)
    if hstate[0] == "bad_pk":
        _tag, layout = hstate
        sigs_all, scalars_all, V, Vp, T, Wv = layout
        RX, RY, RZ, V, Vp = _aggregate_plane(None, layout)
        return _serialize_aggregates(RX, RY, RZ, V), lambda: False
    _tag, V, group_msgs, host = hstate
    with _dispatch_hist.observe_time("finish"):
        ok, xs, sign, inf, sig_red, pk_reds = host
        if not np.asarray(ok).all():
            _raise_bad(ok, "G2")
        out = _g2_emit_bytes(xs, np.asarray(sign).reshape(-1),
                             np.asarray(inf).reshape(-1), V)
        S = PP._host_fold(*sig_red, 2)
        pts = [(m, _unembed_g1(PP._host_fold(*pk_reds[g], 2)))
               for g, m in enumerate(group_msgs)]
    # _pairing_finish times itself as the "verify" phase — keeping it out
    # of the "finish" window is what makes the two separately attributable
    return out, lambda: _pairing_finish(S, pts, hash_fn)


# Pipeline knobs (overridable per instance) resolve through the SlotPolicy
# seam: installed policy → CHARON_TPU_{PIPELINE_DEPTH,FINISH_WORKERS} env →
# defaults. Depth 2 = classic double buffering on the device side: one
# slot executing, one packing — deeper queues only add readback latency.
# finish_workers sizes the stage-3 host executor: the GIL-releasing parts
# (numpy emit, ctypes hash-to-curve + pairing) scale with width, the
# _host_fold bigint adds do not, so small widths capture almost all of
# the overlap.


def _run_emit(ctx, state, inputs, hash_fn):
    """Stage-3 worker body, emit half: fence + readback + byte emission
    inside the submitter's copied contextvars (tracer spans land in the
    submitting duty's trace). Routes through guard.finish_slot_emit so a
    device-class failure rides the fallback ladder on this worker thread
    — off the pipeline lock — instead of surfacing as an error at the
    pop. Returns (aggregates, verify_thunk)."""
    from . import guard

    try:
        with sentinel.region("slot"):
            return ctx.run(guard.finish_slot_emit, state, inputs, hash_fn)
    finally:
        _finish_backlog.inc(amount=-1.0)


def _run_verify(ctx, out, verify):
    """Stage-3 worker body, verify half: run the deferred pairing
    verification thunk (its own chunked device dispatches, with the
    native rung fallback inside _pairing_finish) and assemble the slot's
    public (aggregates, ok) result. Scheduled as a separate executor task
    the moment the emit half completes, so slot N's verify overlaps slot
    N+1's pack and emit."""
    try:
        with sentinel.region("slot"):
            return out, ctx.run(verify)
    finally:
        _verify_backlog.inc(amount=-1.0)


def _settle(fut: Future, value=None, exc: BaseException | None = None):
    """Resolve a watchdog-wrapped future, tolerating a lost race with the
    other resolver (late worker vs fired watchdog)."""
    try:
        if exc is not None:
            fut.set_exception(exc)
        else:
            fut.set_result(value)
    except InvalidStateError:
        pass  # the other side already settled it — their result stands


class SigAggPipeline:
    """Three-stage fused-sigagg pipeline over the _fused_dispatch /
    _fused_readback / _fused_host_finish split.

    Every entry point dispatches through _dispatch_slot: on a host whose
    ops.mesh seam reports >1 device, stage 1 is the SHARDED pack+dispatch
    (validator axis P("data") across the mesh) and stages 2/3 delegate to
    the sharded readback/finish — double-buffering, FIFO, error-at-pop
    and bad_pk semantics are identical either way.

    Stage 1 (host pack + async dispatch) runs on the submitting thread
    under the pipeline lock; stage 2 (device execute) runs on the device's
    own queue; stage 3 (fence + readback + emit, then verify) is scheduled
    onto a small worker executor the moment a slot is dispatched, and is
    itself split over the _fused_emit seam: the emit half (numpy byte
    assembly, RLC host folds — GIL-releasing) settles the slot's
    aggregates and returns a deferred verify thunk, which the pipeline
    chains onto the executor as its own work unit
    (guard.finish_slot_emit). Slot N's verify — batched device pairing
    dispatches on the default-on device path — genuinely overlaps slot
    N+1's emit AND pack AND the in-flight device execute: throughput
    approaches max(pack, execute, emit, verify) instead of
    max(pack + finish, execute). The lock NEVER covers a device sync
    (machine-checked by LINT-TPU-007).

    Usage shapes:

      * submit()/drain() — an explicit FIFO of at most `depth` in-flight
        slots for single-threaded consumers (bench.py's steady-state
        loop). submit() returns the already-FINISHED results of any slots
        popped to keep at most `depth` in flight, oldest first; errors
        (e.g. invalid signatures) re-raise at the pop, same as before.
      * submit_async() — pack + dispatch and return a
        concurrent.futures.Future resolving to THIS slot's (aggregates,
        ok); over-depth backpressure blocks the submitter without
        consuming any other slot's result. The facade's
        threshold_aggregate_verify_submit / core/coalesce ride this. Do
        not mix submit() and submit_async() on one instance — submit()'s
        over-depth pop would steal a future whose owner still holds it.
      * aggregate_verify() — dispatch-then-block for THIS slot (the tbls
        threshold_aggregate_verify shape), finish inline on the calling
        thread: identical blocking semantics and error behavior to the
        two-stage pipeline, no executor hop on the path.
    """

    def __init__(self, depth: int | None = None,
                 finish_workers: int | None = None,
                 slot_deadline: float | None = None,
                 steady_after: int | None = None):
        from . import guard

        # Constructor args PIN a knob (tests, explicit callers); None
        # leaves it policy-managed — resolved now and re-resolvable
        # between slots via apply_policy() when the tuner moves it.
        self._depth_pinned = depth is not None
        self._depth = max(1, policy_mod.pipeline_depth_default()
                          if depth is None else depth)
        self._workers_pinned = finish_workers is not None
        self._workers = max(1, policy_mod.finish_workers_default()
                            if finish_workers is None else finish_workers)
        # Watchdog: slot futures gain a deadline so a hung device fence
        # surfaces as a classified timeout riding the guard's fallback
        # ladder instead of blocking drain() forever. 0 disables.
        self._deadline = (guard.slot_deadline_default()
                          if slot_deadline is None else slot_deadline)
        self._lock = threading.Lock()
        # FIFO of (future, (batches, pks, msgs), hash_fn) in dispatch
        # order — the inputs snapshot is what the watchdog re-packs
        self._pending: deque = deque()
        self._pool: ThreadPoolExecutor | None = None
        # Steady-state sentinel arming (opt-in, CHARON_TPU_STEADY_AFTER or
        # the constructor arg): after `steady_after` dispatched slots the
        # pipeline declares itself warm and arms sentinel.steady_state —
        # from then on, ANY compile anywhere in the process counts as
        # ops_steady_recompile_total, strikes the plane breaker, and trips
        # the sigagg_steady_state_recompile health rule. Disabled by
        # default: callers that legitimately vary slot shapes (tests,
        # ad-hoc batches) must not be punished for recompiling.
        self._steady_after = (sentinel.steady_after_default()
                              if steady_after is None
                              else (steady_after if steady_after > 0
                                    else None))
        self._slots_dispatched = 0
        self._steady_cm = None

    def _note_dispatch(self) -> None:
        # caller holds self._lock. Arms the global steady window once the
        # warmup slot quota is met; the transfer guard is NOT armed here
        # (it is thread-local and the device work runs on workers — the
        # steady tests arm it per-thread via sentinel.transfer_guarded).
        if self._steady_after is None:
            return
        self._slots_dispatched += 1
        if (self._steady_cm is None
                and self._slots_dispatched >= self._steady_after):
            cm = sentinel.steady_state(transfer=None)
            cm.__enter__()
            self._steady_cm = cm

    @property
    def steady_armed(self) -> bool:
        """True once the pipeline has declared itself warm and armed the
        compile sentinel's steady window."""
        with self._lock:
            return self._steady_cm is not None

    @property
    def backlog(self) -> int:
        """Slots submitted but not yet consumed (the ops_sigagg_submit_backlog
        gauge, as a direct accessor for the serving/backpressure layer)."""
        with self._lock:
            return len(self._pending)

    def apply_policy(self, policy=None) -> None:
        """Adopt the installed SlotPolicy's depth/worker knobs between
        slots (registered as a policy_mod.subscribe listener by the tbls
        facade's shared pipeline). Constructor-pinned knobs stay pinned.
        The `policy` arg is the subscriber-callback signature — resolution
        goes through the accessors so env fallbacks apply uniformly."""
        del policy
        with self._lock:
            if not self._depth_pinned:
                self._depth = max(1, policy_mod.pipeline_depth_default())
            if not self._workers_pinned:
                new_w = max(1, policy_mod.finish_workers_default())
                self._workers = new_w
                pool = self._pool
                if pool is not None and new_w > pool._max_workers:
                    # CPython's ThreadPoolExecutor spawns threads lazily
                    # up to _max_workers — raising it widens the pool on
                    # the next submit without rebuilding the executor
                    # (rebuilding would orphan in-flight finish tasks).
                    pool._max_workers = new_w

    def _schedule_finish(self, state, inputs, hash_fn) -> Future:
        # caller holds self._lock; scheduling only — no device sync here.
        # Stage 3 runs as TWO chained executor tasks: the emit half
        # (fence + readback + byte emission, guard-laddered) and, the
        # moment it completes, the verify half (the slot's deferred
        # pairing dispatch). The public future settles after verify, so
        # FIFO / error-at-pop / watchdog semantics are unchanged — but
        # slot N's verify now shares the executor with slot N+1's emit
        # instead of serializing ahead of it.
        if self._pool is None:
            self._pool = ThreadPoolExecutor(
                max_workers=self._workers,
                thread_name_prefix="sigagg-finish")
        _finish_backlog.inc()
        ctx = contextvars.copy_context()
        pool = self._pool
        emit_fut = pool.submit(_run_emit, ctx, state, inputs, hash_fn)
        out_fut: Future = Future()
        out_fut.set_running_or_notify_cancel()

        def _copy_verify(src: Future) -> None:
            exc = src.exception()
            _settle(out_fut, value=None if exc is not None else src.result(),
                    exc=exc)

        def _chain(src: Future) -> None:
            exc = src.exception()
            if exc is not None:
                _settle(out_fut, exc=exc)
                return
            out, verify = src.result()
            _verify_backlog.inc()
            try:
                vfut = pool.submit(_run_verify, ctx, out, verify)
            except RuntimeError:
                # executor already shutting down (close() raced the emit
                # completion): run the verify inline on this worker so
                # the in-flight future still resolves
                try:
                    res = _run_verify(ctx, out, verify)
                except BaseException as vexc:  # noqa: BLE001 — boundary
                    _settle(out_fut, exc=vexc)
                else:
                    _settle(out_fut, value=res)
                return
            vfut.add_done_callback(_copy_verify)

        emit_fut.add_done_callback(_chain)
        return out_fut

    def _pop_result(self, entry):
        """Consume one pending slot's result, watchdog-bounded: a future
        that misses the deadline is abandoned (its worker is stuck on a
        hung fence) and the slot re-runs down the guard ladder on THIS
        thread — outside the lock, so concurrent packs continue."""
        fut, inputs, hash_fn = entry
        if not self._deadline:
            return fut.result()
        try:
            return fut.result(timeout=self._deadline)
        except (_FuturesTimeout, TimeoutError):
            if fut.done():
                raise  # the SLOT raised a timeout (ladder exhausted)
            from . import guard

            return guard.watchdog_recover(inputs, hash_fn)

    def submit(self, batches, pks, msgs, hash_fn=None) -> list:
        """Pack + async-dispatch one slot; its stage-3 finish is scheduled
        immediately on the worker executor. Returns the results of any
        slots popped to keep at most `depth` in flight (oldest first, FIFO
        with every previous submit); pair with drain() for the tail."""
        with tracer.start_span("ops/sigagg_pipeline/submit",
                               slots=len(batches)) as span:
            inputs = (batches, pks, msgs)
            with self._lock:
                state = _dispatch_slot(batches, pks, msgs)
                self._note_dispatch()
                self._pending.append(
                    (self._schedule_finish(state, inputs, hash_fn),
                     inputs, hash_fn))
                over = (self._pending.popleft()
                        if len(self._pending) > self._depth else None)
                span.attrs["in_flight"] = len(self._pending)
                _submit_backlog.set(float(len(self._pending)))
            # block OUTSIDE the lock: the popped slot's finish may still be
            # running on a worker; a concurrent submit packs meanwhile
            return [self._pop_result(over)] if over is not None else []

    def submit_async(self, batches, pks, msgs, hash_fn=None) -> Future:
        """Pack + async-dispatch one slot and return a Future resolving to
        ITS (aggregates, ok) when the stage-3 finish completes (exceptions
        propagate through the future). Applies the same `depth` bound as
        submit() — an over-depth submit blocks until the oldest in-flight
        slot finishes — but never consumes another slot's result, so
        concurrent callers each get exactly their own."""
        with tracer.start_span("ops/sigagg_pipeline/submit",
                               slots=len(batches)) as span:
            inputs = (batches, pks, msgs)
            with self._lock:
                state = _dispatch_slot(batches, pks, msgs)
                self._note_dispatch()
                fut = self._schedule_finish(state, inputs, hash_fn)
                self._pending.append((fut, inputs, hash_fn))
                over = (self._pending.popleft()
                        if len(self._pending) > self._depth else None)
                span.attrs["in_flight"] = len(self._pending)
                _submit_backlog.set(float(len(self._pending)))
            if over is not None:
                # backpressure only: wait, don't .result() — the popped
                # future's owner consumes its value/exception. Deadline-
                # bounded: a hung slot must not wedge every submitter
                # (its own wrapped future watchdog-recovers the result).
                _done, not_done = _futures_wait(
                    [over[0]], timeout=self._deadline or None)
                if not_done:
                    from . import guard

                    guard.note_backpressure_timeout()
            if not self._deadline:
                return fut
            return self._watchdog_wrap(fut, inputs, hash_fn)

    def _watchdog_wrap(self, fut: Future, inputs, hash_fn) -> Future:
        """Clone `fut` onto a deadline: the returned future resolves from
        the worker when it finishes in time, or from the guard ladder on
        a timer thread when the deadline expires first (the stuck inner
        future is abandoned; whichever side settles first wins)."""
        out: Future = Future()
        out.set_running_or_notify_cancel()

        def _copy(src: Future) -> None:
            timer.cancel()
            exc = src.exception()
            _settle(out, value=None if exc is not None else src.result(),
                    exc=exc)

        def _expire() -> None:
            if fut.done():
                return
            from . import guard

            try:
                res = guard.watchdog_recover(inputs, hash_fn)
            except BaseException as exc:  # noqa: BLE001 — future boundary
                _settle(out, exc=exc)
            else:
                _settle(out, value=res)

        timer = threading.Timer(self._deadline, _expire)
        timer.daemon = True
        timer.start()
        fut.add_done_callback(_copy)
        return out

    def drain(self) -> list:
        """Finish every in-flight slot, oldest first (blocking)."""
        out = []
        with tracer.start_span("ops/sigagg_pipeline/drain") as span:
            while True:
                with self._lock:
                    if not self._pending:
                        span.attrs["drained"] = len(out)
                        return out
                    entry = self._pending.popleft()
                    _submit_backlog.set(float(len(self._pending)))
                out.append(self._pop_result(entry))

    def aggregate_verify(self, batches, pks, msgs, hash_fn=None):
        """Dispatch this slot and block for ITS result (the tbls
        threshold_aggregate_verify shape). Only the pack+dispatch holds
        the lock; the fence/readback/finish run inline on the calling
        thread outside it, so concurrent callers overlap their host pack
        with this slot's device execution — and this path never queues
        behind the executor."""
        with tracer.start_span("ops/sigagg_pipeline/aggregate_verify",
                               slots=len(batches)):
            from . import guard

            with self._lock:
                state = _dispatch_slot(batches, pks, msgs)
                self._note_dispatch()
            return guard.finish_slot(state, (batches, pks, msgs), hash_fn)

    def close(self) -> None:
        """Shut the stage-3 executor down (waits for in-flight finishes).
        In-flight futures stay resolvable; the pipeline lazily re-creates
        the executor if used again."""
        with self._lock:
            pool, self._pool = self._pool, None
            cm, self._steady_cm = self._steady_cm, None
            self._slots_dispatched = 0
        if cm is not None:
            cm.__exit__(None, None, None)
        if pool is not None:
            pool.shutdown(wait=True)


@jax.jit
def _g2_affine_std_jit(X, Y, Z):
    """Jacobian G2 plane -> affine standard-form coordinate planes + sign
    and infinity masks, ONE compiled dispatch. The field inversion is a
    batched fixed-exponent power scan (Fq2 inverse via conj/norm), so no
    host bigint inversions remain on the aggregate output path."""
    return _g2_affine_std_core(X, Y, Z)


def _g2_affine_std_core(X, Y, Z):
    z0, z1 = Z[0][None], Z[1][None]
    norm = PP.fe_add(PP._mul_call(z0, z0, 1), PP._mul_call(z1, z1, 1), 1)
    _, inv_bits = _sqrt_inv_bits()
    ninv = PP._pow_scan(norm, jnp.asarray(inv_bits))
    zi = jnp.concatenate([PP._mul_call(z0, ninv, 1)[0][None],
                          PP._mul_call(PP.fe_neg(z1, 1), ninv, 1)[0][None]],
                         axis=0)  # 1/z = conj(z)/|z|²
    zi2 = PP.fe_mul(zi, zi, 2)
    zi3 = PP.fe_mul(zi2, zi, 2)
    xa = PP.fe_mul(X, zi2, 2)
    ya = PP.fe_mul(Y, zi3, 2)
    # standard form for byte emission + sign convention
    S, W = z0.shape[-2:]
    one_raw = _one_raw_plane(S, 2 * W)
    xs = PP._unpack(PP._mul_call(PP._pack(xa)[None], one_raw, 1)[0], 2)
    ys = PP._unpack(PP._mul_call(PP._pack(ya)[None], one_raw, 1)[0], 2)
    inf = jnp.all(Z == 0, axis=(0, 1))
    y0s, y1s = ys[0][None], ys[1][None]
    y1nz = ~jnp.all(y1s == 0, axis=(0, 1))
    sign = jnp.where(y1nz, _gt_half_std(y1s), _gt_half_std(y0s))
    return xs, sign, inf


@jax.jit
def _g1_affine_std_jit(X, Y, Z):
    """Jacobian G1 plane -> affine standard-form x plane + sign/infinity
    masks, one dispatch (the G1 analog of _g2_affine_std_jit; powers the
    batched fixed-base keygen serializer). The field inversion is the
    batched p−2 power scan; Z=0 lanes yield 0^(p-2)=0 and are masked by
    the infinity flag."""
    _, inv_bits = _sqrt_inv_bits()
    zi = PP._pow_scan(Z, jnp.asarray(inv_bits))
    zi2 = PP._mul_call(zi, zi, 1)
    zi3 = PP._mul_call(zi2, zi, 1)
    xa = PP._mul_call(X, zi2, 1)
    ya = PP._mul_call(Y, zi3, 1)
    S, W = X.shape[-2:]
    one_raw = _one_raw_plane(S, W)
    xs = PP._mul_call(xa, one_raw, 1)
    ys = PP._mul_call(ya, one_raw, 1)
    inf = jnp.all(Z == 0, axis=(0, 1))
    sign = _gt_half_std(ys)
    return xs, sign, inf


def _g1_emit_bytes(x_np: np.ndarray, sign_np: np.ndarray,
                   inf_np: np.ndarray, V: int) -> list[bytes]:
    """Standard-form affine G1 x plane + sign/infinity masks -> compressed
    48-byte strings. Bulk numpy byte assembly: flag bits OR'd and infinity
    rows stamped across the whole (V, 48) buffer at once, then C-level
    slicing of one contiguous blob — no per-lane Python byte munging."""
    buf = _fp_limbs_to_be(PP.from_plane(x_np, V))
    return _stamp_flags(buf, sign_np, inf_np, V)


@functools.lru_cache(maxsize=8)
def _gen_plane(Bp: int):
    """Broadcast plane holding the G1 generator in EVERY lane (Montgomery
    Jacobian), cached per bucket — the fixed base of the batched keygen."""
    from ..crypto.curve import to_affine

    ax, ay = to_affine(FqOps, g1_generator())
    X = np.broadcast_to(F.fq_from_int(ax)[None], (Bp, F.LIMBS))
    Y = np.broadcast_to(F.fq_from_int(ay)[None], (Bp, F.LIMBS))
    Z = np.broadcast_to(_MONT_ONE[None], (Bp, F.LIMBS))
    return (jnp.asarray(PP.to_plane(X, 1)), jnp.asarray(PP.to_plane(Y, 1)),
            jnp.asarray(PP.to_plane(Z, 1)))


@jax.jit
def _g1_fixedbase_jit(X, Y, Z, digits):
    pX, pY, pZ = PP._scalar_mul_windowed(X, Y, Z, digits.astype(jnp.int32), 1)
    return _g1_affine_std_jit(pX, pY, pZ)


def g1_mul_gen_batch(scalars: list[int]) -> list[bytes]:
    """Batched fixed-base scalar multiplication kᵢ·G -> compressed bytes,
    one device dispatch for the whole batch + host byte slicing. The FROST
    ceremony's round-1 keygen hot spot (commitments C_ik = a_ik·G and the
    PoK nonces, reference dkg/frost.go:50-86 computes them one
    kryptology scalar-mul at a time): a 6-op × 200-validator ceremony is
    ~5k generator multiplications — exactly the plane's batch shape.
    Bit-identical to the native/serial path (same ETH serialization)."""
    n = len(scalars)
    if n == 0:
        return []
    Bp = _bucket(n)
    X, Y, Z = _gen_plane(Bp)
    digits = jnp.asarray(PP.scalars_to_digitplanes(
        [s % PF.R for s in scalars], Bp))
    xs, sign, inf = _g1_fixedbase_jit(X, Y, Z, digits)
    return _g1_emit_bytes(np.asarray(xs), np.asarray(sign),
                          np.asarray(inf), n)


def _fp_limbs_to_be(limbs: np.ndarray) -> np.ndarray:
    """(n, 32) int32 12-bit limbs -> (n, 48) uint8 big-endian bytes
    (vectorized inverse of _fp_limbs_raw)."""
    lo, hi = limbs[:, 0::2], limbs[:, 1::2]
    b0 = lo & 0xFF
    b1 = ((lo >> 8) & 0xF) | ((hi & 0xF) << 4)
    b2 = (hi >> 4) & 0xFF
    le = np.stack([b0, b1, b2], axis=2).reshape(len(limbs), 48)
    return le[:, ::-1].astype(np.uint8)


def _g2_serialize_device(RX, RY, RZ, V: int) -> list[bytes]:
    xs, sign, inf = _g2_affine_std_jit(RX, RY, RZ)
    return _g2_emit_bytes(np.asarray(xs), np.asarray(sign).reshape(-1),
                          np.asarray(inf).reshape(-1), V)


def _stamp_flags(buf: np.ndarray, sign_np: np.ndarray, inf_np: np.ndarray,
                 V: int) -> list[bytes]:
    """Apply the ETH compressed-point flag byte across a (V, nbytes) uint8
    buffer in bulk and slice it into per-lane bytes objects. Bit-identical
    to the per-lane loop it replaced: 0x80 | (sign << 5) OR'd into byte 0,
    infinity lanes overwritten with the canonical 0xc0 row."""
    sign_np = np.asarray(sign_np).reshape(-1)[:V].astype(bool)
    inf_np = np.asarray(inf_np).reshape(-1)[:V].astype(bool)
    nbytes = buf.shape[1]
    buf[:, 0] |= np.where(sign_np, np.uint8(0xA0), np.uint8(0x80))
    if inf_np.any():
        inf_row = np.zeros(nbytes, np.uint8)
        inf_row[0] = 0xC0
        buf[inf_np] = inf_row
    blob = buf.tobytes()
    return [blob[i * nbytes:(i + 1) * nbytes] for i in range(V)]


def _g2_emit_bytes(x_np: np.ndarray, sign_np: np.ndarray,
                   inf_np: np.ndarray, V: int) -> list[bytes]:
    """Standard-form affine x planes + sign/infinity masks -> compressed
    96-byte strings (shared with the sharded plane). Bulk numpy byte
    assembly — the c1‖c0 concatenation, flag stamping and infinity rows
    all run across the whole (V, 96) buffer; the only per-lane work is
    C-level slicing of one contiguous blob. The stage-3 profile had the
    old per-lane loop at ~1/3 of the finish time for a 1024-lane slot."""
    x0 = _fp_limbs_to_be(PP.from_plane(x_np[0][None], V))
    x1 = _fp_limbs_to_be(PP.from_plane(x_np[1][None], V))
    return _stamp_flags(np.concatenate([x1, x0], axis=1), sign_np, inf_np, V)


def _g2_jacs_to_bytes(jacs: list) -> list[bytes]:
    """Batch-serialize Jacobian G2 points: ONE shared field inversion via
    the Montgomery batch-inverse trick (3(n−1) muls + 1 inversion) instead
    of a per-point fq2_inv on the single host core."""
    from ..crypto.serialize import g2_affine_to_bytes

    nz = [i for i, j in enumerate(jacs) if j[2] != (0, 0)]
    pref, acc = [], (1, 0)
    for i in nz:
        acc = PF.fq2_mul(acc, jacs[i][2])
        pref.append(acc)
    inv = PF.fq2_inv(acc) if nz else None
    invs: dict[int, tuple] = {}
    for k in range(len(nz) - 1, -1, -1):
        i = nz[k]
        invs[i] = PF.fq2_mul(inv, pref[k - 1]) if k else inv
        inv = PF.fq2_mul(inv, jacs[i][2])
    out = []
    for i, j in enumerate(jacs):
        if i in invs:
            zi = invs[i]
            zi2 = PF.fq2_sqr(zi)
            aff = (PF.fq2_mul(j[0], zi2),
                   PF.fq2_mul(j[1], PF.fq2_mul(zi2, zi)))
            out.append(g2_affine_to_bytes(aff))
        else:
            out.append(g2_affine_to_bytes(None))
    return out


# ---------------------------------------------------------------------------
# RLC batch verification
# ---------------------------------------------------------------------------


def _pk_plane_cached(pks: list[bytes], Bp: int) -> PP.PlanePoint:
    """Load + subgroup-check the pubkey plane through the device-resident
    PlaneStore (ops/plane_store.py), memoized by full-set content digest.

    A charon cluster's validator set is static between reconfigurations
    (the share⇄root maps are built once from the cluster lock, reference
    app/app.go:339-383), so every slot verifies against the SAME pubkeys —
    decompressing and subgroup-checking them once per process, not once
    per slot, is the steady-state behavior. Raises ValueError like the
    plane loaders on any invalid/out-of-subgroup pubkey."""
    from . import plane_store

    return plane_store.STORE.full_plane([bytes(p) for p in pks], Bp)


_PK_VALID_CACHE: dict[bytes, bool] = {}
_PK_VALID_CACHE_MAX = 64


def validate_pk_set(pks: list[bytes]) -> None:
    """Reject-infinity + subgroup-check a pubkey set WITHOUT compiling any
    single-device graph — the validation-only sibling of _pk_plane_cached.

    The sharded multichip path needs the same RLC soundness precondition
    (no infinity, r-subgroup membership) but decompresses the pk plane
    INSIDE its own sharded jit, so routing validation through
    _pk_plane_cached would compile the single-device G1 decompress +
    _g1_subgroup_jit graphs as well — the exact modules whose ~6-minute
    cold XLA:CPU compile timed out the round-3/4 driver dryruns
    (MULTICHIP_r04.json). Native ct_g1_check (bls12381.cpp g1_from_bytes
    with subgroup_check=true, bit-identical math, microseconds per key)
    does the job with zero compiles; the device plane check remains the
    fallback when the native library is unavailable. Digest-cached like
    _pk_plane_cached: once per process per pubkey set, not per slot.
    Raises ValueError on any invalid/infinity/out-of-subgroup pubkey."""
    import hashlib

    key = hashlib.sha256(b"".join(pks)).digest()
    if key in _PK_VALID_CACHE:
        return
    try:
        from ..tbls.native_impl import load_library

        lib = load_library()
    except Exception:  # noqa: BLE001 — no native lib → device fallback
        lib = None
    if lib is not None:
        from ..crypto.serialize import g1_finite_compressed

        for i, p in enumerate(pks):
            # finite-compressed flag check (RLC soundness rejects ∞ pks),
            # then native decode+subgroup (bls12381.cpp g1_from_bytes)
            if not g1_finite_compressed(p):
                raise ValueError(f"pubkey {i}: not a finite compressed G1")
            if lib.ct_g1_check(p) != 1:
                raise ValueError(f"pubkey {i}: not a valid subgroup point")
    else:
        _pk_plane_cached(pks, _bucket(len(pks)))
    if len(_PK_VALID_CACHE) >= _PK_VALID_CACHE_MAX:
        _PK_VALID_CACHE.pop(next(iter(_PK_VALID_CACHE)))
    _PK_VALID_CACHE[key] = True


@functools.partial(jax.jit, static_argnames=("G",))
def _g1_groups_sweep_jit(X, Y, Z, rdig, gmask, *, G):
    """ONE windowed sweep (shared short digits) + per-group masked reduces
    over an already-loaded G1 plane, one dispatch — INCLUDING the batched
    subgroup check of every loaded point (RLC soundness, advisor round-4
    high: off-subgroup points with small-order components survive the RLC
    with probability ~1/order; folding the endomorphism check into this
    graph keeps the device path at one dispatch). The FROST batched share
    verification's device core: grouping by commitment degree k lets the
    sweep run on the 64-bit RLC randomizers instead of full 256-bit
    products — 4x fewer windows (frost.verify_shares_batch)."""
    sub_ok = _g1_subgroup_core(X, Y, Z)
    pX, pY, pZ = PP._scalar_mul_windowed(X, Y, Z, rdig.astype(jnp.int32), 1)
    reds = []
    for g in range(G):
        sel = gmask[g][None, None]
        reds.append(PP._reduce_tree_jit(
            jnp.where(sel, pX, 0), jnp.where(sel, pY, 0),
            jnp.where(sel, pZ, 0), 1))
    return reds, sub_ok


@functools.partial(jax.jit, static_argnames=("G",))
def _g1_decode_groups_sweep_jit(Xr, splane, lmask, rdig, gmask, *, G):
    """The FULLY-FUSED FROST share-verification graph: batched G1
    decompression + subgroup check + windowed RLC sweep + per-group masked
    reduces as ONE dispatch — the same one-dispatch shape that took the
    sigagg slot from 4-5 host syncs to one (_fused_slot_jit). Round 4's
    hybrid paid a ~80µs/point native decode on the host; here the sqrt
    scans amortize over the whole plane inside the single dispatch."""
    X, Y, Z, ok = _g1_decompress_core(Xr, splane, lmask)
    sub_ok = _g1_subgroup_core(X, Y, Z)
    pX, pY, pZ = PP._scalar_mul_windowed(X, Y, Z, rdig.astype(jnp.int32), 1)
    reds = []
    for g in range(G):
        sel = gmask[g][None, None]
        reds.append(PP._reduce_tree_jit(
            jnp.where(sel, pX, 0), jnp.where(sel, pY, 0),
            jnp.where(sel, pZ, 0), 1))
    return reds, ok.all(), sub_ok


def g1_groups_msm(points: list[bytes], scalars: list[int],
                  groups: list[int], n_groups: int):
    """Per-group G1 MSMs with SHARED-width short scalars: returns a list of
    n_groups host Jacobians [Σ_{i∈group g} kᵢ·Pᵢ]. scalars are RLC_BITS-bit
    (the sweep runs one 64-bit windowed pass over the whole plane); groups
    assigns each point a group id. Raises ValueError on invalid or
    out-of-subgroup points (RLC soundness: E(Fp)'s cofactor has small
    prime factors, so an off-subgroup point with e.g. an order-3 component
    survives a random linear combination with probability ~1/3 — the check
    is NOT optional for probabilistic verifiers, advisor round-4 high)."""
    n = len(points)
    if not (n == len(scalars) == len(groups)):
        raise ValueError("length mismatch")

    if _device_path(n):
        # TILE-sized chunked dispatches of the fused decompress + subgroup
        # + sweep + reduces graph, with the same ≤TILE graph-size ceiling
        # as the chunked rlc_verify_dispatch. K chunks of the already-compiled
        # ≤TILE-lane graph dispatch back-to-back — jax dispatch is async,
        # so they pipeline on the device — and the per-group partial sums
        # combine on the host (group masks use GLOBAL group ids, so every
        # chunk's g-row means the same group). Nothing compiles at >TILE.
        spans = ([(0, n)] if n <= PP.TILE else
                 [(s, min(s + PP.TILE, n)) for s in range(0, n, PP.TILE)])
        finishers = [_groups_msm_chunk(points, scalars, groups, n_groups,
                                       s, e) for s, e in spans]
        sums: list = [None] * n_groups
        for fin in finishers:
            for g, part in enumerate(fin()):
                sums[g] = part if sums[g] is None else jac_add(
                    FqOps, sums[g], part)
        return sums

    Bp = _bucket(n)
    rdig = jnp.asarray(PP.scalars_to_digitplanes(scalars, Bp,
                                                 nbits=RLC_BITS))
    W = Bp // PP.SUB
    gmask = np.zeros((n_groups, PP.SUB, W), bool)
    for i, g in enumerate(groups):
        gmask[g, i // W, i % W] = True

    # off-device: native bulk decode + (interpret-mode) sweep.
    # reject_infinity matches the device branch above: an ∞ commitment is
    # a degenerate dealer polynomial (kryptology rejects identity points),
    # and as the RLC identity element it would otherwise pass silently.
    plane = g1_plane_from_compressed([bytes(p) for p in points], Bp,
                                     device_decode=False,
                                     reject_infinity=True)
    reds, sub_ok = _g1_groups_sweep_jit(plane.X, plane.Y, plane.Z, rdig,
                                        jnp.asarray(gmask), G=n_groups)
    if not bool(sub_ok):  # checked inside the same dispatch as the sweep
        raise ValueError("G1 point not in subgroup")
    return [PP._host_fold(*red, 1) for red in reds]


def _groups_msm_chunk(points, scalars, groups, n_groups: int,
                      s: int, e: int):
    """Parse + ASYNC-dispatch one ≤TILE-lane chunk [s:e) of the fused
    groups-MSM graph; returns a finisher that blocks on the chunk and
    yields its n_groups host partial Jacobians (groups absent from the
    chunk fold to infinity, which jac_add absorbs). Split out as the chunk
    seam so tests can stub it with a host oracle — the fused graph itself
    only compiles at device/nightly shapes. Parse rejects infinity
    commitments up front (an ∞ commitment is a degenerate dealer
    polynomial; the reference's per-item check fails it too since
    kryptology rejects identity points)."""
    nc = e - s
    Bc = _bucket(nc)
    rdig = jnp.asarray(PP.scalars_to_digitplanes(scalars[s:e], Bc,
                                                 nbits=RLC_BITS))
    W = Bc // PP.SUB
    gmask = np.zeros((n_groups, PP.SUB, W), bool)
    for i, g in enumerate(groups[s:e]):
        gmask[g, i // W, i % W] = True
    body, _fin, sgn, loaded = _parse_compressed(
        [bytes(p) for p in points[s:e]], 48, "G1", True, Bc)
    reds, ok, sub_ok = _g1_decode_groups_sweep_jit(
        jnp.asarray(_raw_to_plane(body, Bc)), jnp.asarray(sgn),
        jnp.asarray(loaded), rdig, jnp.asarray(gmask), G=n_groups)

    def finish():
        if not bool(ok):
            raise ValueError("invalid G1 point encoding")
        if not bool(sub_ok):
            raise ValueError("G1 point not in subgroup")
        return [PP._host_fold(*red, 1) for red in reds]

    return finish


def g1_lincomb_is_infinity(points: list[bytes], scalars: list[int]) -> bool:
    """Σ kᵢ·Pᵢ == ∞ over compressed G1 points with PER-POINT 256-bit
    scalars, as one windowed MSM sweep + reduce on the device. This is the
    FROST ceremony's batched share-verification check (dkg/frost.py
    verify_shares_batch): the t×n VSS consistency equations collapse under
    an RLC into exactly this wide-batch G1 MSM — the shape the plane is
    built for (SURVEY §7 step 8; reference dkg/frost.go:50-86 verifies
    share-by-share on the CPU instead). Raises ValueError on an invalid
    point encoding OR an out-of-subgroup point: the ∞ comparison is only
    2^-RLC_BITS-sound over the prime subgroup — an off-subgroup commitment
    with a small-order component (cofactor divisible by 3) passes the RLC
    with probability ~1/order, so decoding must subgroup-check (advisor
    round-4 high; the ValueError routes callers to exact per-item
    attribution, same as any invalid encoding)."""
    n = len(points)
    if n == 0:
        return True
    if len(scalars) != n:
        raise ValueError("length mismatch")
    Bp = _bucket(n)
    # reject_infinity: same rationale as g1_groups_msm — an ∞ point is the
    # RLC identity and would vanish from the equation instead of failing
    plane = g1_plane_from_compressed([bytes(p) for p in points], Bp,
                                     check_subgroup=True,
                                     reject_infinity=True)
    digits = PP.scalars_to_digitplanes([s % PF.R for s in scalars], Bp)
    S = PP.msm_sum(plane, digits)
    return jac_is_infinity(FqOps, S)


def rlc_verify_batch(pks: list[bytes], msgs: list[bytes], sigs: list[bytes],
                     hash_fn=None) -> bool:
    """Batch-verify compressed (pk, msg, sig) triples with one device MSM
    sweep + one native multi-pairing. Curve membership and infinity
    rejection are enforced in the bulk decode (reference BLS verify
    semantics; ct_verify's jac_is_inf gate); SUBGROUP membership — which
    RLC soundness requires — is enforced by the batched device endomorphism
    checks (g{1,2}_subgroup_ok) below. hash_fn(msg) -> G2 Jacobian
    (defaults to the native C++ hash-to-curve, which emits the compressed
    point directly). Returns overall validity; no per-item attribution
    (callers fall back to per-item checks on failure)."""
    n = len(msgs)
    if n == 0:
        return True
    if not (len(pks) == len(sigs) == n):
        raise ValueError("length mismatch")
    Bp = _bucket(n)

    if not _device_path(n):
        try:
            sig_plane = g2_plane_from_compressed(sigs, Bp,
                                                 reject_infinity=True)
            pk_plane = _pk_plane_cached(pks, Bp)
        except ValueError:
            return False
        if not g2_subgroup_ok(sig_plane):
            return False
        return _rlc_check(sig_plane, pk_plane, msgs, hash_fn)

    # device: decompression + subgroup + combined MSMs as ONE dispatch and
    # one transfer per TILE-sized CHUNK (_verify_slot_jit). Chunking is the
    # graph-size ceiling: the fused verify graph's op count (dominated by
    # the subgroup check's unrolled endomorphism chains) grows with the
    # lane count, so every width past one tile would be one more large
    # compile.
    # K chunks of the ALREADY-COMPILED ≤1024-lane production graphs are
    # dispatched back-to-back — jax dispatch is async, so the chunks
    # pipeline on the device with no host sync between them — and the
    # per-chunk RLC partial sums combine on the host with K-1 Jacobian
    # adds (the RLC equation is a sum; splitting lanes splits the sum).
    # Nothing ever compiles at >TILE lanes.
    with sentinel.region("verify"):
        state = rlc_verify_dispatch(pks, msgs, sigs)
        return rlc_verify_finish(state, hash_fn)


def rlc_verify_dispatch(pks, msgs, sigs):
    """Host parse + ASYNC device dispatch of one verify batch; returns the
    pending state for rlc_verify_finish. Callers overlap the next batch's
    host parse (or any host work) with this batch's device execution —
    the parsigex steady state, mirroring _fused_dispatch/_fused_finish on
    the sigagg side. Device path only (rlc_verify_batch gates)."""
    n = len(msgs)
    chunks = ([(0, n)] if n <= PP.TILE else
              [(s, min(s + PP.TILE, n)) for s in range(0, n, PP.TILE)])
    pending = []
    try:
        # every chunk's plane is keyed on the FULL-set digest + span in the
        # PlaneStore — a fixed peer set decodes once per process, not once
        # per slot (the old per-chunk `pks[s:e]` content keys churned the
        # whole-set-sized LRU every slot, ADVICE round 5). The span covers
        # the digest, the lookup and, on a miss, the decode with its waits.
        from . import plane_store

        with tracer.start_span("ops/verify/pk_planes", chunks=len(chunks)):
            pk_planes = plane_store.STORE.chunk_planes(
                [bytes(p) for p in pks], chunks)
        # host work up to the launch of each chunk's verify program
        with tracer.start_span("ops/verify/pack", lanes=n) as span:
            # distinct-message groups are GLOBAL so chunk g-indices agree
            index = _group_index(msgs)
            _gidx, G, group_msgs = index
            span.attrs["groups"] = G
            for ci, (s, e) in enumerate(chunks):
                nc = e - s
                Bc = _bucket(nc)
                body, _fin, sgn, loaded = _parse_compressed(
                    sigs[s:e], 96, "G2", True, Bc)
                pk_plane = pk_planes[ci]
                X0r = jnp.asarray(_raw_to_plane(body[:, 48:], Bc))
                X1r = jnp.asarray(_raw_to_plane(body[:, :48], Bc))
                rdig = jnp.asarray(PP.scalars_to_digitplanes(
                    sample_randomizers(nc), Bc, nbits=RLC_BITS))
                _keys, gmask = _group_masks(msgs[s:e], nc, Bc, index=index)
                pending.append(_verify_slot_jit(
                    X0r, X1r, jnp.asarray(sgn), jnp.asarray(loaded), rdig,
                    pk_plane.X, pk_plane.Y, pk_plane.Z, jnp.asarray(gmask),
                    G=G))
    except ValueError:
        return ("invalid",)
    return ("pending", G, group_msgs, pending)


def rlc_verify_finish(state, hash_fn=None) -> bool:
    """Block on the dispatched chunks, combine the per-chunk RLC sums and
    run the multi-pairing."""
    if state[0] == "invalid":
        return False
    _tag, G, group_msgs, pending = state
    S = None
    Pg: list = [None] * G
    # the host folds of the chunks' reductions into one Jacobian sum each;
    # the wait for each chunk's program is the nested ops/device_wait
    with tracer.start_span("ops/verify/fold", chunks=len(pending)):
        for outs in pending:
            with tracer.start_span("ops/device_wait",
                                   program="_verify_slot_jit"):
                ok, sub_ok, sig_red, pk_reds = jax.device_get(outs)
            if not (ok.all() and sub_ok):
                return False
            sc = PP._host_fold(*sig_red, 2)
            S = sc if S is None else jac_add(Fq2Ops, S, sc)
            for g in range(G):
                pc = PP._host_fold(*pk_reds[g], 2)
                Pg[g] = pc if Pg[g] is None else jac_add(Fq2Ops, Pg[g], pc)
        pts = [(m, _unembed_g1(Pg[g])) for g, m in enumerate(group_msgs)]
    return _pairing_finish(S, pts, hash_fn)


def _rlc_dispatch(sig_plane: PP.PlanePoint, pk_plane: PP.PlanePoint,
                  msgs: list[bytes]):
    """Issue the RLC MSM device work ASYNCHRONOUSLY and return the pending
    state. Callers can overlap host work (e.g. aggregate serialization)
    between dispatch and _rlc_finish. Padding lanes beyond len(msgs) carry
    zero randomizers (∞ contributions)."""
    n = len(msgs)
    Bp = sig_plane.B
    # one uint8 digit transfer shared by the sig and pk MSM dispatches;
    # randomizers drawn as one vectorized batch (crypto/rlc)
    digits = jnp.asarray(PP.scalars_to_digitplanes(
        sample_randomizers(n), Bp, nbits=RLC_BITS))

    sig_red = PP._msm_reduce_jit(sig_plane.X, sig_plane.Y, sig_plane.Z,
                                 digits, 2)

    groups: dict[bytes, list[int]] = {}
    for i, m in enumerate(msgs):
        groups.setdefault(bytes(m), []).append(i)

    pk_reds: list[tuple[bytes, tuple]] = []
    if len(groups) == 1:
        m = next(iter(groups))
        pk_reds.append((m, PP._msm_reduce_jit(
            pk_plane.X, pk_plane.Y, pk_plane.Z, digits, 1)))
    else:
        pX, pY, pZ = PP._scalar_mul_windowed(
            pk_plane.X, pk_plane.Y, pk_plane.Z,
            digits.astype(jnp.int32), 1)
        for m, idxs in groups.items():
            mask = np.zeros(Bp, dtype=bool)
            mask[idxs] = True
            mplane = jnp.asarray(
                mask.reshape(PP.SUB, Bp // PP.SUB)[None, None])
            mX = jnp.where(mplane, pX, 0)
            mY = jnp.where(mplane, pY, 0)
            mZ = jnp.where(mplane, pZ, 0)
            pk_reds.append((m, PP._reduce_tree_jit(mX, mY, mZ, 1)))
    return sig_red, pk_reds


def _combined_msm(SIGX, SIGY, SIGZ, pkX, pkY, pkZ, rdig, gmask, G):
    """Sig-G2 MSM and pk-G1 MSM through ONE windowed sweep: the G1 plane is
    embedded into Fq2 with zero c1 (the Jacobian add/double formulas are
    curve- and field-extension-agnostic, and (a,0)x(b,0)=(ab,0), so the
    embedded lanes compute exact G1 arithmetic) and concatenated onto the
    lane axis. Narrow MSMs are launch-latency-bound, so halving the number
    of kernel launches ~halves the MSM wall time. Returns the reduced sig
    plane and G per-group reduced (embedded) pk planes."""
    W = SIGX.shape[-1]
    pk2 = [jnp.concatenate([c, c * 0], axis=0) for c in (pkX, pkY, pkZ)]
    CX = jnp.concatenate([SIGX, pk2[0]], axis=-1)
    CY = jnp.concatenate([SIGY, pk2[1]], axis=-1)
    CZ = jnp.concatenate([SIGZ, pk2[2]], axis=-1)
    cdig = jnp.concatenate([rdig, rdig], axis=-1).astype(jnp.int32)
    mX, mY, mZ = PP._scalar_mul_windowed(CX, CY, CZ, cdig, 2)
    sig_red = PP._reduce_tree_jit(mX[..., :W], mY[..., :W], mZ[..., :W], 2)
    pmX, pmY, pmZ = mX[..., W:], mY[..., W:], mZ[..., W:]
    pk_reds = []
    for g in range(G):
        sel = gmask[g][None, None]
        pk_reds.append(PP._reduce_tree_jit(
            jnp.where(sel, pmX, 0), jnp.where(sel, pmY, 0),
            jnp.where(sel, pmZ, 0), 2))
    return sig_red, pk_reds


@functools.partial(jax.jit, static_argnames=("T", "Wv", "G"))
def _fused_slot_jit(X0r, X1r, sgn, lmask, ldigits, rdig, pkX, pkY, pkZ,
                    gmask, *, T, Wv, G):
    """The WHOLE fused sigagg slot as one dispatch: G2 decompression ->
    windowed Lagrange sweep + combine -> affine serialization front-half,
    plus the combined sig+pk RLC MSMs — so the host pays ONE dispatch and
    ONE blocking transfer per slot instead of four or five."""
    X, Y, Z, ok = _g2_decompress_core(X0r, X1r, sgn, lmask)
    RX, RY, RZ = _sweep_combine_core(X, Y, Z, ldigits, T, Wv)
    xs, sign, inf = _g2_affine_std_core(RX, RY, RZ)
    sig_red, pk_reds = _combined_msm(RX, RY, RZ, pkX, pkY, pkZ,
                                     rdig, gmask, G)
    return ok, xs, sign, inf, sig_red, pk_reds


@functools.partial(jax.jit, static_argnames=("G",))
def _verify_slot_jit(X0r, X1r, sgn, lmask, rdig, pkX, pkY, pkZ, gmask, *, G):
    """rlc_verify_batch as one dispatch: G2 decompression + batched
    endomorphism subgroup check + combined sig+pk MSMs, one transfer."""
    X, Y, Z, ok = _g2_decompress_core(X0r, X1r, sgn, lmask)
    sub_ok = _g2_subgroup_core(X, Y, Z)
    sig_red, pk_reds = _combined_msm(X, Y, Z, pkX, pkY, pkZ, rdig, gmask, G)
    return ok, sub_ok, sig_red, pk_reds


def _group_index(msgs):
    """First-seen distinct-message index -> (gidx, G, keys): group id per
    message, the group count padded up to a power of two with EMPTY
    groups, and the key list padded to G with b"". Shared by the per-slot
    and chunked verify paths (see _group_masks for the pow-2 rationale)."""
    gidx: dict[bytes, int] = {}
    for m in msgs:
        gidx.setdefault(bytes(m), len(gidx))
    G = 1
    while G < len(gidx):
        G *= 2
    return gidx, G, list(gidx) + [b""] * (G - len(gidx))


def _group_masks(msgs, n: int, Bp: int, index=None):
    """Distinct-message groups + (G, 8, W) lane masks (padding lanes are in
    no group). G is padded up to a power of two with EMPTY groups so the
    jitted slot graphs specialize on O(log) distinct G values instead of
    recompiling per slot (a cold compile costs minutes; an all-false mask
    yields an infinity pk sum, which the pairing finish soundly skips —
    the same rule that handles degenerate real groups).

    index: optional (gidx, G, keys) from _group_index over the GLOBAL
    message list — chunked callers pass it so every chunk's mask row g
    means the same message (msgs is then just this chunk's slice)."""
    gidx, G, keys = index if index is not None else _group_index(msgs)
    W = Bp // PP.SUB
    gmask = np.zeros((G, PP.SUB, W), bool)
    for i, m in enumerate(msgs):
        gmask[gidx[bytes(m)], i // W, i % W] = True
    return keys, gmask


def _unembed_g1(jac2):
    """Fq2-embedded G1 Jacobian (host ints) -> G1 Jacobian; the c1
    components of an embedded-lane computation are identically zero."""
    (x0, x1), (y0, y1), (z0, z1) = jac2
    assert x1 == 0 and y1 == 0 and z1 == 0, "embedded G1 left the base field"
    return (x0, y0, z0)


def _rlc_finish(state, hash_fn=None) -> bool:
    """Await the dispatched MSMs (host fold) and run the multi-pairing."""
    sig_red, pk_reds = state
    S = PP._host_fold(*sig_red, 2)
    pts = []
    for m, red in pk_reds:
        pts.append((m, PP._host_fold(*red, 1)))
    return _pairing_finish(S, pts, hash_fn)


# ---------------------------------------------------------------------------
# Bounded process-wide H(m) hash-to-curve cache. A duty's signing root is
# hashed to G2 on partial-signature receipt (parsigex/validatorapi verify)
# and AGAIN at aggregate verify — and every node in the cluster re-verifies
# the same few distinct roots per slot. ct_hash_to_g2 is ~0.2 ms of native
# work per call; the cache keys on the exact message bytes (H(m) depends on
# nothing else — domain separation is fixed inside the native lib), so a
# hit is always byte-identical to a recompute. LRU-bounded: signing roots
# are unbounded over time but only a handful are live per slot.
# ---------------------------------------------------------------------------

_H2C_CAP = policy_mod.h2c_cache_cap_default()
_h2c_lock = threading.Lock()
# msg bytes -> [96-byte compressed, (hx, hy) affine limb planes | None].
# The compressed form feeds the native fallback rung; the limb planes are
# what the device pairing kernel consumes — hits hand them back directly
# instead of re-decompressing 96 bytes per verify.
_h2c_cache: OrderedDict = OrderedDict()
_h2c_counter = metrics.counter(
    "ops_hash_to_g2_cache_total",
    "H(m) hash-to-curve cache lookups in _pairing_finish", ("result",))


def set_h2c_cache_cap(cap: int) -> int:
    """Set the H(m) cache bound (evicting down if needed); returns the
    previous cap. cap <= 0 disables caching entirely."""
    global _H2C_CAP
    with _h2c_lock:
        prev, _H2C_CAP = _H2C_CAP, cap
        while len(_h2c_cache) > max(cap, 0):
            _h2c_cache.popitem(last=False)
    return prev


def _h2c_store(key: bytes, comp: bytes, planes) -> None:
    with _h2c_lock:
        if _H2C_CAP <= 0:
            return
        entry = _h2c_cache.get(key)
        if entry is None:
            _h2c_cache[key] = [comp, planes]
        elif planes is not None and entry[1] is None:
            entry[1] = planes
        _h2c_cache.move_to_end(key)
        while len(_h2c_cache) > _H2C_CAP:
            _h2c_cache.popitem(last=False)


def _hash_to_g2_native(key: bytes) -> bytes:
    """The cache's native miss path, extracted so the bytes and planes
    accessors share it: compressed H(m) via ctypes ct_hash_to_g2. This is
    the ONE sanctioned ct_hash_to_g2 call site in ops/ (LINT-TPU-012)."""
    out96 = (ctypes.c_uint8 * 96)()
    _native_lib().ct_hash_to_g2(key, len(key), out96)
    return bytes(out96)


def hash_to_g2_cached(m: bytes) -> bytes:
    """Compressed H(m) through the bounded LRU; native ct_hash_to_g2 on a
    miss. Thread-safe — stage-3 finish workers and API verify threads
    share one cache (a double-computed miss under a race is harmless: both
    sides store the identical bytes)."""
    key = bytes(m)
    with _h2c_lock:
        entry = _h2c_cache.get(key)
        if entry is not None:
            _h2c_cache.move_to_end(key)
    if entry is not None:
        _h2c_counter.inc("hit")
        return entry[0]
    _h2c_counter.inc("miss")
    out = _hash_to_g2_native(key)
    _h2c_store(key, out, None)
    return out


def _planes_from_compressed(comp: bytes):
    """Host decompress of a cached 96-byte H(m) into affine limb planes —
    the plane-less entry upgrade path (entries first filled by the native
    bytes accessor). The point was produced by hash-to-curve, so the
    subgroup re-check is skipped."""
    from ..crypto.curve import to_affine
    from ..crypto.serialize import g2_from_bytes

    aff = to_affine(Fq2Ops, g2_from_bytes(comp, subgroup_check=False))
    return (F.fq2_from_ints(*aff[0]).astype(np.int32),
            F.fq2_from_ints(*aff[1]).astype(np.int32))


def hash_to_g2_planes(msgs):
    """Device-ready affine H(m) limb planes for a message batch: (hx, hy)
    numpy arrays of shape (B, 2, L). Cache hits (including plane-less
    entries stored by the bytes accessor, upgraded in place) count as
    "hit"; misses compute the hash — ONE bucketed device h2c dispatch for
    the whole miss set when the device verify path is up, the native
    bytes rung plus host decompress otherwise — and store both forms."""
    with tracer.start_span("ops/verify/h2c") as span:
        return _hash_to_g2_planes(msgs, span)


def _hash_to_g2_planes(msgs, span):
    from ..crypto.serialize import g2_affine_to_bytes

    B = len(msgs)
    L = F.LIMBS
    hx = np.zeros((B, 2, L), np.int32)
    hy = np.zeros((B, 2, L), np.int32)
    derive: list[tuple[int, bytes, bytes]] = []   # (idx, key, compressed)
    missing: list[tuple[int, bytes]] = []
    with _h2c_lock:
        for i, m in enumerate(msgs):
            key = bytes(m)
            entry = _h2c_cache.get(key)
            if entry is None:
                missing.append((i, key))
                continue
            _h2c_cache.move_to_end(key)
            if entry[1] is None:
                derive.append((i, key, entry[0]))
            else:
                hx[i], hy[i] = entry[1]
    span.attrs.update(hits=B - len(missing), misses=len(missing))
    if B - len(missing):
        _h2c_counter.inc("hit", amount=float(B - len(missing)))
    for i, key, comp in derive:
        planes = _planes_from_compressed(comp)
        hx[i], hy[i] = planes
        _h2c_store(key, comp, planes)
    if not missing:
        return hx, hy
    _h2c_counter.inc("miss", amount=float(len(missing)))
    if _verify_device_path():
        from . import h2c as h2c_mod

        # hash_to_g2_device chunks internally at h2c.MAX_BATCH, so a miss
        # set wider than one tile (the default-on, unbounded-pair regime)
        # never feeds an oversized batch into the bucketed graph family
        # (regression-pinned by test_device_verify).
        mx, my = h2c_mod.hash_to_g2_device([k for _, k in missing])
        for j, (i, key) in enumerate(missing):
            planes = (mx[j], my[j])
            hx[i], hy[i] = planes
            aff = (F.fq2_to_ints(mx[j]), F.fq2_to_ints(my[j]))
            _h2c_store(key, g2_affine_to_bytes(aff), planes)
    else:
        for i, key in missing:
            comp = _hash_to_g2_native(key)
            planes = _planes_from_compressed(comp)
            hx[i], hy[i] = planes
            _h2c_store(key, comp, planes)
    return hx, hy


def _device_pairing_check(S, live, plan=None) -> bool:
    """One batched device dispatch for a slot's verification: H(m) limb
    planes from the upgraded cache (bucketed device h2c on the miss set),
    every pair's Miller loop on its own batch lane, a single final
    exponentiation on the RLC-folded Fq12 product. The signature pair
    rides as (−g1, S) — negation folded into the G1 y-coordinate. Shards
    the pair axis across the mesh when one is up; a multi-host `plan`
    (the dispatching slot's HostPlan) keys the cluster verify's exchange
    on that slot's sequence number."""
    from ..crypto.curve import to_affine
    from . import pairing as pairing_mod

    L = F.LIMBS
    n = len(live) + 1
    p_x = np.empty((n, L), np.int32)
    p_y = np.empty((n, L), np.int32)
    q_x = np.empty((n, 2, L), np.int32)
    q_y = np.empty((n, 2, L), np.int32)
    q_x[:n - 1], q_y[:n - 1] = hash_to_g2_planes([m for m, _ in live])
    for i, (_m, P) in enumerate(live):
        ax, ay = to_affine(FqOps, P)
        p_x[i] = F.fq_from_int(ax)
        p_y[i] = F.fq_from_int(ay)
    p_x[-1] = F.fq_from_int(pairing_mod._G1_NEG[0])
    p_y[-1] = F.fq_from_int(pairing_mod._G1_NEG[1])
    sx, sy = to_affine(Fq2Ops, S)
    q_x[-1] = F.fq2_from_ints(*sx)
    q_y[-1] = F.fq2_from_ints(*sy)

    from . import mesh as mesh_mod

    mesh = mesh_mod.sigagg_mesh()
    if mesh is not None:
        from . import sharded_plane

        return sharded_plane.sharded_pairing_check(p_x, p_y, q_x, q_y, mesh,
                                                   plan=plan)
    return pairing_mod.pairing_check_planes(p_x, p_y, q_x, q_y)


def _native_pairing_finish(S, live, hash_fn=None) -> bool:
    """The verify ladder's native rung: compressed-byte pairs through the
    guard's ctypes multi-pairing seam — same verdicts as the device path,
    reached on interpret hosts, guard fallback, or a custom hash_fn."""
    g1_pts, g2_pts, negs = [], [], []
    for m, P in live:
        g1_pts.append(g1_to_bytes(P))
        if hash_fn is None:
            g2_pts.append(hash_to_g2_cached(m))
        else:
            g2_pts.append(g2_to_bytes(hash_fn(m)))
        negs.append(0)
    g1_pts.append(g1_to_bytes(g1_generator()))
    g2_pts.append(g2_to_bytes(S))
    negs.append(1)
    _pairing_c.inc("native", amount=float(len(negs)))
    from . import guard

    # inputs here are derived from already-validated points — skip the
    # per-pair subgroup scalar-muls inside the pairing decode
    return guard.native_pairing_check(
        b"".join(g1_pts), b"".join(g2_pts), bytes(negs))


def _pairing_finish(S, group_points, hash_fn=None, plan=None) -> bool:
    """Multi-pairing over host Jacobians: S = Σ rᵢ·sigᵢ (G2) and per
    distinct message m its P_m = Σ rᵢ·pkᵢ (G1). The whole check is the
    "verify" phase of ops_device_dispatch_seconds: one batched device
    dispatch (h2c + multi-Miller-loop + final exp) on the device path,
    degrading through guard.note_verify_fallback to the native
    ct_pairing_check rung on a device-class failure — same verdicts
    either way, split by ops_pairing_total{path}. A caller-injected
    hash_fn (test paths) always takes the native rung. `plan` is the
    dispatching slot's sharded_plane.HostPlan: threaded into the device
    check so a multi-host cluster verify exchanges under the slot's own
    sequence tag (worker threads race; tags must not be call-ordered)."""
    with _dispatch_hist.observe_time("verify"), \
            tracer.start_span("ops/verify/pairing") as span:
        live = []
        for m, P in group_points:
            if jac_is_infinity(FqOps, P):
                # degenerate pk combination: only consistent with S lacking
                # any contribution from this group — the pairing check below
                # still has to balance, so simply omit the vanished pair
                continue
            live.append((bytes(m), P))
        span.attrs["pairs"] = len(live) + 1
        if jac_is_infinity(Fq2Ops, S):
            # all signatures were infinity: valid only if every pk side
            # vanished too
            return not live
        if hash_fn is None and _verify_device_path():
            from . import guard

            if guard.BREAKER.state != guard.OPEN:
                try:
                    ok = _device_pairing_check(S, live, plan=plan)
                except Exception as exc:  # degrade to the native rung
                    reason = guard.classify(exc)
                    if reason == "input":
                        raise
                    guard.note_verify_fallback(reason, exc)
                else:
                    _pairing_c.inc("device", amount=float(len(live) + 1))
                    span.attrs["path"] = "device"
                    return ok
        span.attrs["path"] = "native"
        return _native_pairing_finish(S, live, hash_fn)


def warm_verify_graphs(flush_at: int | None = None) -> int:
    """AOT-compile the device verify graphs a production slot actually
    hits into the persistent JAX compile cache so the first slot doesn't
    eat the trace. Buckets are derived from the configured slot shape:
    `flush_at` defaults to the coalescer's TILE × device-count window, so
    the warm set covers the small-slot pairing bucket (2: one message
    group + the signature pair), the largest monolithic bucket a
    ≤flush_at slot compiles, the chunked family (TILE-lane Miller+fold
    chunks plus the cross-chunk finish) when flush_at+1 pairs overflow
    one tile, and the matching h2c miss-set buckets (1 and the capped
    flush bucket). Returns the number of graphs lowered.

    EXPLICITLY returns 0 without lowering anything when the device verify
    path is off (CHARON_TPU_DEVICE_VERIFY=0) — callers treat both the 0
    and any raise as advisory and skip the warm."""
    if not _verify_device_path():
        return 0
    from . import h2c as h2c_mod
    from . import mesh as mesh_mod
    from . import pairing as pairing_mod

    sentinel.install()
    if flush_at is None:
        flush_at = PP.TILE * max(1, mesh_mod.device_count())
    with sentinel.region("warm"):
        tile = pairing_mod.MAX_PAIR_TILE
        pairs = flush_at + 1  # every message distinct + the signature pair
        buckets = {2, min(tile, pairing_mod._bucket_pairs(pairs))}
        n = pairing_mod.warm_check_buckets(tuple(sorted(buckets)))
        if pairs > tile:
            n_chunks = -(-pairs // tile)
            n += pairing_mod.warm_chunk_graphs(
                chunk_buckets=(tile,),
                finish_buckets=(pairing_mod._bucket_pairs(n_chunks),))
        h2c_buckets = {1, min(h2c_mod.MAX_BATCH, pairing_mod._bucket_pairs(
            flush_at))}
        n += h2c_mod.warm_buckets(tuple(sorted(h2c_buckets)))
        return n


def _rlc_check(sig_plane: PP.PlanePoint, pk_plane: PP.PlanePoint,
               msgs: list[bytes], hash_fn=None) -> bool:
    """The RLC core over already-loaded planes: shared-digit MSMs + one
    native multi-pairing."""
    return _rlc_finish(_rlc_dispatch(sig_plane, pk_plane, msgs), hash_fn)
