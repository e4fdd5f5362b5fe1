"""The G2 cofactor clear of device hash-to-curve (ops/h2c): the RFC 9380
Appendix G.3 chain that `_clear_cofactor` runs as a tape, against the
plain [h_eff] multiplication of crypto/curve.g2_clear_cofactor.

The host test interprets the very tape array the device graph scans, with
the host point ops, so it pins the chain, its sign conventions and the ψ
constants in well under a second; the device test runs the compiled tape
on a small batch."""

from __future__ import annotations

import functools
import random

import jax
import numpy as np
import pytest

from charon_tpu.crypto import curve as PC
from charon_tpu.crypto import fields as PF
from charon_tpu.crypto.curve import (B_G2, Fq2Ops, g2_clear_cofactor,
                                     g2_in_subgroup, jac_infinity, to_affine,
                                     to_jacobian)
from charon_tpu.ops import h2c


def _host_psi(pt):
    cx, cy = PC.psi_consts()
    X, Y, Z = pt
    return (PF.fq2_mul(PF.fq2_conj(X), cx), PF.fq2_mul(PF.fq2_conj(Y), cy),
            PF.fq2_conj(Z))


_HOST_MAPS = {
    h2c._ID: lambda p: p,
    h2c._NEG: lambda p: PC.jac_neg(Fq2Ops, p),
    h2c._PSI: _host_psi,
    h2c._PSI2: lambda p: _host_psi(_host_psi(p)),
}
_HOST_OPS = {
    h2c._DBL: lambda p, q: PC.jac_double(Fq2Ops, p),
    h2c._ADD: lambda p, q: PC.jac_add(Fq2Ops, p, q),
}


def _run_tape_on_host(pt):
    regs = [pt] + [jac_infinity(Fq2Ops)] * (h2c._N_PT_REGS - 1)
    for dst, a, m, op, b in h2c._COFACTOR_TAPE.tolist():
        regs[dst] = _HOST_OPS[op](_HOST_MAPS[m](regs[a]), regs[b])
    return regs[h2c._R_Q]


@functools.lru_cache(maxsize=None)
def _off_subgroup_points(n: int, seed: int):
    """n seeded points of E2 outside the r-torsion subgroup."""
    rng = random.Random(seed)
    out = []
    while len(out) < n:
        x = (rng.randrange(PF.P), rng.randrange(PF.P))
        y = PF.fq2_sqrt(PF.fq2_add(PF.fq2_mul(PF.fq2_sqr(x), x), B_G2))
        if y is None:
            continue
        pt = to_jacobian(Fq2Ops, (x, y))
        if not g2_in_subgroup(pt):
            out.append(pt)
    return tuple(out)


def _same_point(p, q) -> bool:
    return to_affine(Fq2Ops, p) == to_affine(Fq2Ops, q)


def test_tape_shape():
    """Two [x]· chains of 63 doublings and 5 additions, plus ψ(P) + t1 and
    the four closing steps."""
    ops = h2c._COFACTOR_TAPE[:, 3]
    assert len(h2c._COFACTOR_TAPE) == 2 * (63 + 5) + 1 + 4
    assert int((ops == h2c._DBL).sum()) == 2 * 63 + 1
    assert int((ops == h2c._ADD).sum()) == 2 * 5 + 1 + 3


@pytest.mark.parametrize("idx", range(8))
def test_tape_matches_h_eff_off_subgroup(idx):
    pt = _off_subgroup_points(8, seed=26)[idx]
    got = _run_tape_on_host(pt)
    assert _same_point(got, g2_clear_cofactor(pt))
    assert g2_in_subgroup(got)


def test_tape_matches_h_eff_in_g2():
    pt = PC.jac_mul(Fq2Ops, PC.g2_generator(), 0x1234567890ABCDEF)
    assert _same_point(_run_tape_on_host(pt), g2_clear_cofactor(pt))


def test_tape_keeps_infinity():
    assert to_affine(Fq2Ops, _run_tape_on_host(jac_infinity(Fq2Ops))) is None


def test_device_clear_cofactor_matches_host():
    """The compiled tape on a batch of two off-subgroup points, in affine
    form, against the host h_eff oracle."""
    from charon_tpu.ops import curve as DC
    from charon_tpu.ops import field as DF

    pts = _off_subgroup_points(2, seed=2026)
    planes = [np.stack(c) for c in zip(*(DC.g2_point_to_device(p)
                                         for p in pts))]
    X, Y, Z = jax.jit(h2c._clear_cofactor)(tuple(planes))
    for i, pt in enumerate(pts):
        got = (DF.fq2_to_ints(np.asarray(X[i])),
               DF.fq2_to_ints(np.asarray(Y[i])),
               DF.fq2_to_ints(np.asarray(Z[i])))
        assert _same_point(got, g2_clear_cofactor(pt))
