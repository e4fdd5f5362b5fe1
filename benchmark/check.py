"""How `correct` is decided: every answered set against the reference.

A set's verdict must equal the reference's: True exactly when every
signature in it verifies under its pubkey. The reference decides that by
recomputing each signature from the benchmark's own secret key
(reference.ref.verifies: BLS signatures are unique).

Every number compared is a count whose limit is 0: sets that got no answer,
verdicts that differ.
"""

from __future__ import annotations

from reference import ref

LIMITS = {"unanswered": 0, "verdict_mismatches": 0}


def expected(req) -> bool:
    return all(ref.verifies(s, k, m)
               for s, k, m in zip(req.sigs, req.secrets, req.roots))


def compare(outcomes) -> dict[str, int]:
    """The counts compared against LIMITS over every submitted set."""
    counts = dict.fromkeys(LIMITS, 0)
    for o in outcomes:
        if o.answer is None:
            counts["unanswered"] += 1
        else:
            counts["verdict_mismatches"] += int(bool(o.answer)
                                                != expected(o.request))
    return counts


def report(counts: dict[str, int]) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) in a fixed order."""
    checks = {k: {"value": counts[k], "limit": LIMITS[k]} for k in LIMITS}
    return all(counts[k] <= LIMITS[k] for k in LIMITS), checks
