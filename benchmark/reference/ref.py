"""The benchmark's plain reference for BLS signatures over BLS12-381.

Pure Python, written from the published definitions; it imports nothing of
the system under test and takes none of its tables:

* the curve from its parameter x = -0xd201000000010000: the base field's
  prime p = (x - 1)^2 (x^4 - x^2 + 1) / 3 + x, the group order
  r = x^4 - x^2 + 1, E1: y^2 = x^3 + 4 over Fp and E2: y^2 = x^3 + 4(1 + i)
  over Fp2 = Fp[i] / (i^2 + 1);
* the generators from their standard compressed encodings;
* hash-to-G2 by RFC 9380 (suite BLS12381G2_XMD:SHA-256_SSWU_RO_, section
  8.8.2): expand_message_xmd with SHA-256, the simplified SWU map onto the
  3-isogenous curve E2': y^2 = x^3 + 240i x + 1012(1 + i) with Z = -(2 + i),
  the 3-isogeny of appendix E.3 written as the exact fractions its
  coefficients are, and cofactor clearing by the endomorphism psi
  (appendix G.3), whose constants are worked out from p;
* the compressed point encoding of the Ethereum consensus specs.

benchmark/tests/test_reference.py pins it to RFC 9380's known answer
(appendix J.10.1) and to the standard generator encodings.

The benchmark uses it to make its inputs from the seed (keys, partial
signatures) and to decide what each answer should have been. BLS signatures
are unique: a G2 point verifies for message m under the public key [sk]G1
exactly when it equals [sk]H(m). So the reference decides validity by
recomputing the signature from the benchmark's own secret key. Fixed-base
tables make the many multiplications of one base (the G1 generator, or one
root's H(m)) cheap.
"""

from __future__ import annotations

import hashlib
import threading
from functools import lru_cache

X = -0xD201000000010000
P = (X - 1) ** 2 * (X**4 - X**2 + 1) // 3 + X
R = X**4 - X**2 + 1

# the signature ciphersuite of the Ethereum consensus specs (proof of
# possession scheme)
DST = b"BLS_SIG_BLS12381G2_XMD:SHA-256_SSWU_RO_POP_"


# --------------------------------------------------------------------------
# fields
# --------------------------------------------------------------------------


def q(num: int, den: int = 1) -> int:
    """The fraction num/den in Fp."""
    return num * pow(den, -1, P) % P


def fp_sqrt(a: int) -> int | None:
    s = pow(a, (P + 1) // 4, P)  # p = 3 mod 4
    return s if s * s % P == a % P else None


class Fp:
    zero, one = 0, 1

    @staticmethod
    def add(a, b):
        return (a + b) % P

    @staticmethod
    def sub(a, b):
        return (a - b) % P

    @staticmethod
    def neg(a):
        return -a % P

    @staticmethod
    def mul(a, b):
        return a * b % P

    @staticmethod
    def sqr(a):
        return a * a % P

    @staticmethod
    def small(a, k):
        return a * k % P

    @staticmethod
    def inv(a):
        return pow(a, -1, P)


class Fp2:
    """a0 + a1 i as the pair (a0, a1)."""

    zero, one = (0, 0), (1, 0)

    @staticmethod
    def add(a, b):
        return ((a[0] + b[0]) % P, (a[1] + b[1]) % P)

    @staticmethod
    def sub(a, b):
        return ((a[0] - b[0]) % P, (a[1] - b[1]) % P)

    @staticmethod
    def neg(a):
        return (-a[0] % P, -a[1] % P)

    @staticmethod
    def mul(a, b):
        t0, t1 = a[0] * b[0], a[1] * b[1]
        return ((t0 - t1) % P, ((a[0] + a[1]) * (b[0] + b[1]) - t0 - t1) % P)

    @staticmethod
    def sqr(a):
        return ((a[0] + a[1]) * (a[0] - a[1]) % P, 2 * a[0] * a[1] % P)

    @staticmethod
    def small(a, k):
        return (a[0] * k % P, a[1] * k % P)

    @staticmethod
    def inv(a):
        n = pow(a[0] * a[0] + a[1] * a[1], -1, P)
        return (a[0] * n % P, -a[1] * n % P)

    @staticmethod
    def conj(a):
        return (a[0], -a[1] % P)

    @staticmethod
    def pow(a, e: int):
        out = Fp2.one
        for bit in bin(e)[2:]:
            out = Fp2.sqr(out)
            if bit == "1":
                out = Fp2.mul(out, a)
        return out

    @staticmethod
    def is_square(a) -> bool:
        # a is a square in Fp2 exactly when its norm a0^2 + a1^2 is one in Fp
        n = (a[0] * a[0] + a[1] * a[1]) % P
        return n == 0 or pow(n, (P - 1) // 2, P) == 1

    @staticmethod
    def sqrt(a):
        """A square root of a, or None; from a square root of the norm."""
        a0, a1 = a
        if a1 == 0:
            s = fp_sqrt(a0)
            if s is not None:
                return (s, 0)
            s = fp_sqrt(-a0 % P)
            return None if s is None else (0, s)
        n = fp_sqrt((a0 * a0 + a1 * a1) % P)
        if n is None:
            return None
        half = (P + 1) // 2
        x0 = fp_sqrt((a0 + n) * half % P)
        if x0 is None:
            x0 = fp_sqrt((a0 - n) * half % P)
            if x0 is None:
                return None
        return (x0, a1 * pow(2 * x0, -1, P) % P)

    @staticmethod
    def sgn0(a) -> int:
        """RFC 9380 section 4.1 for m = 2."""
        return (a[0] & 1) | (int(a[0] == 0) & a[1] & 1)


# --------------------------------------------------------------------------
# curves, in Jacobian coordinates (X, Y, Z) for x = X/Z^2, y = Y/Z^3
# --------------------------------------------------------------------------

B1 = 4
B2 = (4, 4)


def is_inf(F, p) -> bool:
    return p[2] == F.zero


def dbl(F, p):
    X1, Y1, Z1 = p
    if Z1 == F.zero:
        return p
    A, B = F.sqr(X1), F.sqr(Y1)
    C = F.sqr(B)
    D = F.small(F.sub(F.sub(F.sqr(F.add(X1, B)), A), C), 2)
    E = F.small(A, 3)
    X3 = F.sub(F.sqr(E), F.small(D, 2))
    Y3 = F.sub(F.mul(E, F.sub(D, X3)), F.small(C, 8))
    return (X3, Y3, F.small(F.mul(Y1, Z1), 2))


def add(F, p, r):
    if p[2] == F.zero:
        return r
    if r[2] == F.zero:
        return p
    X1, Y1, Z1 = p
    X2, Y2, Z2 = r
    Z1Z1, Z2Z2 = F.sqr(Z1), F.sqr(Z2)
    U1, U2 = F.mul(X1, Z2Z2), F.mul(X2, Z1Z1)
    S1 = F.mul(F.mul(Y1, Z2), Z2Z2)
    S2 = F.mul(F.mul(Y2, Z1), Z1Z1)
    H = F.sub(U2, U1)
    rr = F.small(F.sub(S2, S1), 2)
    if H == F.zero:
        return dbl(F, p) if rr == F.zero else (F.one, F.one, F.zero)
    I = F.sqr(F.small(H, 2))
    J = F.mul(H, I)
    V = F.mul(U1, I)
    X3 = F.sub(F.sub(F.sqr(rr), J), F.small(V, 2))
    Y3 = F.sub(F.mul(rr, F.sub(V, X3)), F.small(F.mul(S1, J), 2))
    Z3 = F.mul(F.sub(F.sub(F.sqr(F.add(Z1, Z2)), Z1Z1), Z2Z2), H)
    return (X3, Y3, Z3)


def add_affine(F, p, a):
    """p + a for an affine a = (x, y)."""
    if p[2] == F.zero:
        return (a[0], a[1], F.one)
    X1, Y1, Z1 = p
    Z1Z1 = F.sqr(Z1)
    U2 = F.mul(a[0], Z1Z1)
    S2 = F.mul(F.mul(a[1], Z1), Z1Z1)
    H = F.sub(U2, X1)
    rr = F.small(F.sub(S2, Y1), 2)
    if H == F.zero:
        return dbl(F, p) if rr == F.zero else (F.one, F.one, F.zero)
    HH = F.sqr(H)
    I = F.small(HH, 4)
    J = F.mul(H, I)
    V = F.mul(X1, I)
    X3 = F.sub(F.sub(F.sqr(rr), J), F.small(V, 2))
    Y3 = F.sub(F.mul(rr, F.sub(V, X3)), F.small(F.mul(Y1, J), 2))
    Z3 = F.sub(F.sub(F.sqr(F.add(Z1, H)), Z1Z1), HH)
    return (X3, Y3, Z3)


def neg(F, p):
    return (p[0], F.neg(p[1]), p[2])


def mul(F, p, k: int):
    """[k]p by double and add; k may be negative."""
    if k < 0:
        p, k = neg(F, p), -k
    out = (F.one, F.one, F.zero)
    for bit in bin(k)[2:]:
        out = dbl(F, out)
        if bit == "1":
            out = add(F, out, p)
    return out


def lincomb(F, points, scalars):
    """The sum of [k]p over the pairs, by Straus's method with 4-bit
    windows: one chain of doublings shared by every point."""
    tables = []
    for p in points:
        row = [p]
        for _ in range(14):
            row.append(add(F, row[-1], p))
        tables.append(row)
    ks = [k % R for k in scalars]
    out = (F.one, F.one, F.zero)
    for w in range(-(-max(k.bit_length() for k in ks) // 4) - 1, -1, -1):
        for _ in range(4):
            out = dbl(F, out)
        for row, k in zip(tables, ks):
            d = (k >> (4 * w)) & 15
            if d:
                out = add(F, out, row[d - 1])
    return out


def to_affine(F, p):
    if p[2] == F.zero:
        return None
    zi = F.inv(p[2])
    zi2 = F.sqr(zi)
    return (F.mul(p[0], zi2), F.mul(F.mul(p[1], zi2), zi))


def batch_affine(F, pts):
    """Affine forms of finite Jacobian points, with one inversion."""
    acc, pre = F.one, []
    for p in pts:
        pre.append(acc)
        acc = F.mul(acc, p[2])
    inv = F.inv(acc)
    out = [None] * len(pts)
    for i in range(len(pts) - 1, -1, -1):
        zi = F.mul(inv, pre[i])
        inv = F.mul(inv, pts[i][2])
        zi2 = F.sqr(zi)
        out[i] = (F.mul(pts[i][0], zi2), F.mul(F.mul(pts[i][1], zi2), zi))
    return out


def on_curve(F, b, a) -> bool:
    x, y = a
    return F.sqr(y) == F.add(F.mul(F.sqr(x), x), b)


# --------------------------------------------------------------------------
# encodings (compressed, big-endian, flags in the first byte's top bits:
# 0x80 compressed, 0x40 infinity, 0x20 the larger y)
# --------------------------------------------------------------------------

HALF = (P - 1) // 2


def g1_compress(p) -> bytes:
    a = to_affine(Fp, p)
    if a is None:
        return bytes([0xC0]) + bytes(47)
    out = bytearray(a[0].to_bytes(48, "big"))
    out[0] |= 0x80 | (0x20 if a[1] > HALF else 0)
    return bytes(out)


def g2_compress(p) -> bytes:
    a = to_affine(Fp2, p)
    if a is None:
        return bytes([0xC0]) + bytes(95)
    (x0, x1), (y0, y1) = a
    big = y1 > HALF if y1 else y0 > HALF
    out = bytearray(x1.to_bytes(48, "big") + x0.to_bytes(48, "big"))
    out[0] |= 0x80 | (0x20 if big else 0)
    return bytes(out)


def _flags(data: bytes, n: int):
    if len(data) != n or not data[0] & 0x80 or data[0] & 0x40:
        raise ValueError("not a compressed finite point")
    return bool(data[0] & 0x20), bytes([data[0] & 0x1F]) + data[1:]


def g1_decompress(data: bytes):
    big, body = _flags(data, 48)
    x = int.from_bytes(body, "big")
    y = fp_sqrt((x * x * x + B1) % P) if x < P else None
    if y is None:
        raise ValueError("not on E1")
    if (y > HALF) != big:
        y = P - y
    return (x, y, 1)


def g2_decompress(data: bytes):
    big, body = _flags(data, 96)
    x1, x0 = int.from_bytes(body[:48], "big"), int.from_bytes(body[48:], "big")
    if x0 >= P or x1 >= P:
        raise ValueError("not a field element")
    x = (x0, x1)
    y = Fp2.sqrt(Fp2.add(Fp2.mul(Fp2.sqr(x), x), B2))
    if y is None:
        raise ValueError("not on E2")
    if (y[1] > HALF if y[1] else y[0] > HALF) != big:
        y = Fp2.neg(y)
    return (x, y, Fp2.one)


G1_HEX = ("97f1d3a73197d7942695638c4fa9ac0fc3688c4f9774b905a14e3a3f171bac58"
          "6c55e83ff97a1aeffb3af00adb22c6bb")
G2_HEX = ("93e02b6052719f607dacd3a088274f65596bd0d09920b61ab5da61bbdc7f5049"
          "334cf11213945d57e5ac7d055d042b7e024aa2b2f08f0a91260805272dc51051"
          "c6e47ad4fa403b02b4510b647ae3d1770bac0326a805bbefd48056c8c121bdb8")
G1 = g1_decompress(bytes.fromhex(G1_HEX))
G2 = g2_decompress(bytes.fromhex(G2_HEX))


# --------------------------------------------------------------------------
# hash to G2 (RFC 9380)
# --------------------------------------------------------------------------


def expand_message_xmd(msg: bytes, dst: bytes, n: int) -> bytes:
    """RFC 9380 section 5.3.1 with SHA-256 (b = 32, s = 64 bytes)."""
    ell = -(-n // 32)
    if ell > 255 or len(dst) > 255:
        raise ValueError("expand_message_xmd: out of range")
    dst_prime = dst + bytes([len(dst)])
    b0 = hashlib.sha256(bytes(64) + msg + n.to_bytes(2, "big") + b"\x00"
                        + dst_prime).digest()
    blocks = [hashlib.sha256(b0 + b"\x01" + dst_prime).digest()]
    for i in range(2, ell + 1):
        mixed = bytes(a ^ b for a, b in zip(b0, blocks[-1]))
        blocks.append(hashlib.sha256(mixed + bytes([i]) + dst_prime).digest())
    return b"".join(blocks)[:n]


def hash_to_field(msg: bytes, dst: bytes, count: int = 2):
    """RFC 9380 section 5.2 for Fp2: L = 64 bytes per coordinate."""
    u = expand_message_xmd(msg, dst, count * 2 * 64)
    return [tuple(int.from_bytes(u[64 * (j + 2 * i):64 * (j + 2 * i + 1)],
                                 "big") % P for j in range(2))
            for i in range(count)]


A_ISO = (0, 240)
B_ISO = (1012, 1012)
Z_SSWU = (-2 % P, -1 % P)


def map_to_curve_sswu(u):
    """RFC 9380 section 6.6.2 onto E2', affine."""
    zu2 = Fp2.mul(Z_SSWU, Fp2.sqr(u))
    tv1 = Fp2.add(Fp2.sqr(zu2), zu2)
    if tv1 == Fp2.zero:
        x1 = Fp2.mul(B_ISO, Fp2.inv(Fp2.mul(Z_SSWU, A_ISO)))
    else:
        x1 = Fp2.mul(Fp2.mul(Fp2.neg(B_ISO), Fp2.inv(A_ISO)),
                     Fp2.add(Fp2.one, Fp2.inv(tv1)))

    def g(x):
        return Fp2.add(Fp2.mul(Fp2.add(Fp2.sqr(x), A_ISO), x), B_ISO)

    gx1 = g(x1)
    if Fp2.is_square(gx1):
        x, y = x1, Fp2.sqrt(gx1)
    else:
        x = Fp2.mul(zu2, x1)
        y = Fp2.sqrt(g(x))
    if Fp2.sgn0(u) != Fp2.sgn0(y):
        y = Fp2.neg(y)
    return (x, y)


def _c(re: tuple[int, int], im: tuple[int, int]):
    return (q(*re), q(*im))


# The 3-isogeny E2' -> E2 of RFC 9380 appendix E.3, each coefficient k_(j,i)
# (of x^i) written as the fraction it is: x = x_num / x_den and
# y = y' * y_num / y_den, the denominators monic.
ISO_X_NUM = [_c((304, 9), (304, 9)), _c((0, 1), (-8, 3)),
             _c((4, 3), (-4, 3)), _c((1, 9), (0, 1))]
ISO_X_DEN = [_c((0, 1), (-72, 1)), _c((12, 1), (-12, 1)), Fp2.one]
ISO_Y_NUM = [_c((752, 27), (752, 27)), _c((0, 1), (88, 9)),
             _c((-2, 3), (2, 3)), _c((-1, 27), (0, 1))]
ISO_Y_DEN = [_c((-432, 1), (-432, 1)), _c((0, 1), (-216, 1)),
             _c((18, 1), (-18, 1)), Fp2.one]


def _poly(coeffs, x):
    acc = Fp2.zero
    for c in reversed(coeffs):
        acc = Fp2.add(Fp2.mul(acc, x), c)
    return acc


def iso_map(a):
    x, y = a
    xo = Fp2.mul(_poly(ISO_X_NUM, x), Fp2.inv(_poly(ISO_X_DEN, x)))
    yo = Fp2.mul(y, Fp2.mul(_poly(ISO_Y_NUM, x), Fp2.inv(_poly(ISO_Y_DEN, x))))
    return (xo, yo, Fp2.one)


# psi = untwist, Frobenius, twist (RFC 9380 appendix G.3)
PSI_X = Fp2.inv(Fp2.pow((1, 1), (P - 1) // 3))
PSI_Y = Fp2.inv(Fp2.pow((1, 1), (P - 1) // 2))


def psi(p):
    return (Fp2.mul(PSI_X, Fp2.conj(p[0])), Fp2.mul(PSI_Y, Fp2.conj(p[1])),
            Fp2.conj(p[2]))


def clear_cofactor(p):
    """[h_eff]p = [x^2 - x - 1]p + [x - 1]psi(p) + psi^2([2]p)."""
    t = add(Fp2, mul(Fp2, p, X * X - X - 1), mul(Fp2, psi(p), X - 1))
    return add(Fp2, t, psi(psi(dbl(Fp2, p))))


def hash_to_g2(msg: bytes, dst: bytes = DST):
    """H(msg), a Jacobian point of G2 (RFC 9380 hash_to_curve)."""
    u0, u1 = hash_to_field(msg, dst, 2)
    s = add(Fp2, iso_map(map_to_curve_sswu(u0)),
            iso_map(map_to_curve_sswu(u1)))
    return clear_cofactor(s)


# --------------------------------------------------------------------------
# fixed-base multiplication, keys and signatures
# --------------------------------------------------------------------------

WINDOW = 8


class FixedBase:
    """[k]base for many k: the affine multiples d * 2^(8j) * base for every
    byte d and position j, so that [k]base costs one addition per byte."""

    def __init__(self, F, base):
        self.F = F
        rows, b = [], base
        for _ in range(-(-R.bit_length() // WINDOW)):
            row = [b]
            for _ in range(2, 1 << WINDOW):
                row.append(add(F, row[-1], b))
            rows.append(row)
            b = dbl(F, row[(1 << (WINDOW - 1)) - 1])
        flat = batch_affine(F, [p for row in rows for p in row])
        n = (1 << WINDOW) - 1
        self.rows = [flat[i:i + n] for i in range(0, len(flat), n)]

    def mul(self, k: int):
        k %= R
        out = (self.F.one, self.F.one, self.F.zero)
        mask, j = (1 << WINDOW) - 1, 0
        while k:
            d = k & mask
            if d:
                out = add_affine(self.F, out, self.rows[j][d - 1])
            k >>= WINDOW
            j += 1
        return out


_lock = threading.Lock()
_g1_table: FixedBase | None = None


def _g1() -> FixedBase:
    global _g1_table
    with _lock:
        if _g1_table is None:
            _g1_table = FixedBase(Fp, G1)
        return _g1_table


@lru_cache(maxsize=16)
def _h(root: bytes) -> FixedBase:
    return FixedBase(Fp2, hash_to_g2(root))


def pubkey(sk: int) -> bytes:
    """[sk]G1, compressed (48 bytes)."""
    return g1_compress(_g1().mul(sk))


def sign(sk: int, msg: bytes) -> bytes:
    """The BLS signature [sk]H(msg), compressed (96 bytes)."""
    return g2_compress(_h(msg).mul(sk))


def sign_many(msg: bytes, sks: list[int]) -> list[bytes]:
    table = _h(msg)
    return [g2_compress(table.mul(sk)) for sk in sks]


def verifies(sig: bytes, sk: int, msg: bytes) -> bool:
    """Whether sig verifies for msg under the public key [sk]G1: BLS
    signatures are unique, so exactly when it is [sk]H(msg)."""
    return sig == sign(sk, msg)


def lagrange_at_zero(ids: list[int]) -> list[int]:
    """Lagrange coefficients at x = 0 for the share indices `ids`, mod r."""
    out = []
    for i in ids:
        num, den = 1, 1
        for j in ids:
            if j != i:
                num = num * j % R
                den = den * (j - i) % R
        out.append(num * pow(den, -1, R) % R)
    return out
