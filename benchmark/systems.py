"""What the closed loop drives: the program's tbls entry point that the
mix's `entry` names (`ENTRIES`), or, for the control and the fault tests,
the reference put in the program's place.

A system turns a request into the arguments of its call in set-up
(`prepare`), and `submit(args)` returns a Future of the answer: a peer
set's verdict, or a slot's (aggregates, verdict).
"""

from __future__ import annotations

import random
from concurrent.futures import Future

import inputs
from reference import ref

SAMPLE = 8  # the control verifies one partial (or aggregate) in this many


def _resolved(fn, *args) -> Future:
    fut: Future = Future()
    try:
        fut.set_result(fn(*args))
    except Exception as exc:  # noqa: BLE001 — the future carries it
        fut.set_exception(exc)
    return fut


class ProgramSystem:
    """The TPU backend of charon_tpu's tbls facade, installed as the node
    installs it (feature tpu_bls), with device faults raised rather than
    served from the native fallback."""

    name = "program"

    def __init__(self, chips: int):
        from charon_tpu import tbls
        from charon_tpu.app.app import Config, _select_tbls_backend
        from charon_tpu.ops import mesh as mesh_mod
        from charon_tpu.ops import pallas_plane as PP
        from charon_tpu.tbls.tpu_impl import TPUImpl

        if chips == 1:
            mesh_mod.set_override(1)  # one chip, even on a four-chip host
        if not _select_tbls_backend(Config(feature_set_enable=["tpu_bls"])):
            raise RuntimeError("tpu_bls did not select the device path")
        impl = tbls.get_implementation()
        if not isinstance(impl, TPUImpl):
            raise RuntimeError(f"tbls backend is {type(impl).__name__}")
        impl.fallback_on_device_error = False
        if PP._interpret():
            raise RuntimeError("Pallas kernels would run interpreted")
        if mesh_mod.device_count() != chips:
            raise RuntimeError(f"mesh is {mesh_mod.device_count()} wide, the "
                               f"cell asks for {chips}")
        self.tbls = tbls

    def prepare(self, req):
        from charon_tpu.tbls.types import PublicKey, Signature

        return ([PublicKey(p) for p in req.pks], list(req.roots),
                [Signature(s) for s in req.sigs])

    def submit(self, args) -> Future:
        return _resolved(lambda: bool(self.tbls.verify_batch(*args)))

    def close(self) -> None:
        pass


class SlotSystem(ProgramSystem):
    """core/sigagg's call for one duty of a slot
    (tbls.threshold_aggregate_verify_submit, core/sigagg.py): threshold
    partials per validator, the group pubkeys and the signing roots; its
    Future resolves to (aggregates, verdict)."""

    def prepare(self, req):
        from charon_tpu.tbls.types import Signature

        return ([{i: Signature(s) for i, s in b.items()}
                 for b in req.partials], list(req.pks), list(req.roots))

    def submit(self, args) -> Future:
        return self.tbls.threshold_aggregate_verify_submit(*args)


# entry name in a mix -> the system that drives it
ENTRIES = {
    "tbls.verify_batch": ProgramSystem,
    inputs.SLOT_ENTRY: SlotSystem,
}


def program_system(mix: dict):
    """The class that drives the mix's entry; stops the run on an unknown
    one."""
    entry = mix.get("entry")
    if entry not in ENTRIES:
        raise SystemExit(f"benchmark: the mix's entry {entry!r} is not one "
                         f"of {sorted(ENTRIES)}")
    return ENTRIES[entry]


class ReferenceSystem:
    """The reference in the program's place, for peer sets and for slots.

    A slot's aggregates are the Lagrange combinations (ref.lagrange_at_zero)
    of each validator's partials, and its verdict whether every aggregate
    is the group key's signature of the root.

    `control` makes it the control: it verifies one partial (one aggregate)
    in SAMPLE, from an offset drawn from `seed`, which breaks the guarantee
    that every partial is verified. `fault` plants one of the faults a timed
    path can have: "stale" (returns the previous request's answer), "half"
    (leaves out the second half of the set; of a slot's validators, whose
    aggregates are left as their first partial), "altered" (alters the
    answer where it is made: the verdict of a set, one aggregate's bytes of
    a slot)."""

    name = "reference"

    def __init__(self, control: bool = False, fault: str | None = None,
                 seed: int = 0):
        self.step = SAMPLE if control else 1
        self.fault = fault
        self.rng = random.Random(seed)
        self.last = None

    def prepare(self, req):
        return req

    def _answer(self, req) -> bool:
        n = req.size // 2 if self.fault == "half" else req.size
        ok = all(ref.verifies(req.sigs[i], req.secrets[i], req.roots[i])
                 for i in range(self.rng.randrange(self.step), n, self.step))
        return not ok if self.fault == "altered" else ok

    def _slot_answer(self, req) -> tuple[list[bytes], bool]:
        n = req.size // 2 if self.fault == "half" else req.size
        lam = ref.lagrange_at_zero(list(req.ids))
        aggs = [ref.g2_compress(ref.lincomb(
            ref.Fp2, [ref.g2_decompress(b[i]) for i in req.ids], lam))
            for b in req.partials[:n]]
        aggs += [b[req.ids[0]] for b in req.partials[n:]]
        ok = all(ref.verifies(aggs[v], req.secrets[v], req.roots[v])
                 for v in range(self.rng.randrange(self.step), n, self.step))
        if self.fault == "altered":
            aggs[0] = aggs[0][:-1] + bytes([aggs[0][-1] ^ 1])
        return aggs, ok

    def submit(self, req) -> Future:
        def call():
            if isinstance(req, inputs.SlotRequest):
                ans = self._slot_answer(req)
            else:
                ans = self._answer(req)
            if self.fault == "stale" and self.last is not None:
                ans, self.last = self.last, ans
            else:
                self.last = ans
            return ans

        return _resolved(call)

    def close(self) -> None:
        pass
