"""How `correct` is decided: every answered request against the reference.

A peer set's verdict must equal the reference's: True exactly when every
signature in it verifies under its pubkey. A slot's verdict must be True
exactly when every partial it aggregates verifies over the root, and each
validator whose partials all do must get the bytes of its group key's
signature of the root, [secret]H(root): BLS signatures are unique, so those
bytes are the reference's aggregate. The reference decides validity by
recomputing each signature from the benchmark's own secret key
(reference.ref.verifies).

Every number compared is a count whose limit is 0: requests that got no
answer, verdicts that differ, and (for slots) aggregates that differ.
"""

from __future__ import annotations

import inputs
from reference import ref

LIMITS = {"unanswered": 0, "verdict_mismatches": 0,
          "aggregate_mismatches": 0}
# a peer set's checks: it has no aggregates
SET_CHECKS = ("unanswered", "verdict_mismatches")


def expected(req) -> bool:
    return all(ref.verifies(s, k, m)
               for s, k, m in zip(req.sigs, req.secrets, req.roots))


def _slot_counts(req, answer) -> dict[str, int]:
    aggs, ok = answer
    valid = [all(ref.verifies(b[i], sh[i], m) for i in b)
             for b, sh, m in zip(req.partials, req.shares, req.roots)]
    if len(aggs) != req.size:
        mismatches = req.size
    else:
        mismatches = sum(good and bytes(a) != ref.sign(k, m) for good, a, k, m
                         in zip(valid, aggs, req.secrets, req.roots))
    return {"verdict_mismatches": int(bool(ok) != all(valid)),
            "aggregate_mismatches": mismatches}


def compare(outcomes) -> dict[str, int]:
    """The counts compared over every submitted request, in the order they
    are reported."""
    slots = any(isinstance(o.request, inputs.SlotRequest) for o in outcomes)
    counts = dict.fromkeys(LIMITS if slots else SET_CHECKS, 0)
    for o in outcomes:
        if o.answer is None:
            counts["unanswered"] += 1
        elif slots:
            for k, v in _slot_counts(o.request, o.answer).items():
                counts[k] += v
        else:
            counts["verdict_mismatches"] += int(bool(o.answer)
                                                != expected(o.request))
    return counts


def report(counts: dict[str, int]) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) in the counts' order."""
    checks = {k: {"value": v, "limit": LIMITS[k]} for k, v in counts.items()}
    return all(v <= LIMITS[k] for k, v in counts.items()), checks
