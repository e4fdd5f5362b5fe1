"""Seeded inputs: a cluster's keys and the peer sets a traffic mix sends.

One general generator. A configuration file fixes the deployment (validators,
nodes, threshold, this node's share); a mix file fixes the traffic (the
duties of a slot, how many slots an epoch has, the warm sets). Every seed
gets the same sizes in the same order; the seed picks the keys, which
validators attest in which slot, the roots, the order in which the peers'
sets arrive and the warm set's bad partials.

The consensus specs give every validator one attestation slot per epoch, so
a slot's attesters are a seeded partition of the validators into
`slots_per_epoch` groups of equal size. For each duty of a slot (each names
one signing root, fresh per slot, shared by all the slot's attesters), every
peer sends one set: its partial signature for each of the slot's attesters,
with its share pubkeys. The node verifies each set with one call.

The warm sets come first, from a slot of their own: the first
`warm_bad_sets` of them each have `bad_partials` partials made over another
root, in their second half, so that a verifier which skips partials (a
sample, half the set) can pass them; the last is valid. Every set of the
window is valid.

A mix whose entry aggregates (`SLOT_ENTRY`, core/sigagg's call) asks
instead for one request per duty of a slot: for each of the slot's
attesters, the partials that reached threshold first, over the slot's root.
Those are the node's own share and the first `threshold - 1` peers of the
slot's seeded arrival order, the same for every validator of the slot, so
the id sets vary from slot to slot. Its warm requests are `warm_sets` whole
slots of their own; in the first `warm_bad_sets` of them the first duty has
`bad_partials` validators in its second half holding one partial over
another root.

Adapted from the seeded cluster of the repository's chip smoke script; keys
are made and signed by the benchmark's own reference (reference/ref.py).
"""

from __future__ import annotations

import random
from dataclasses import dataclass

from reference import ref

SLOT_ENTRY = "tbls.threshold_aggregate_verify_submit"


@dataclass
class Request:
    """One peer's set: one call into the system under test, with what the
    reference needs to decide its answer."""

    index: int
    slot: int
    duty: str
    peer: int
    pks: list[bytes]
    roots: list[bytes]            # per signature
    sigs: list[bytes]
    secrets: list[int]            # per signature, for the reference
    bad: int = 0                  # partials made over another root
    last_of_slot: bool = False

    @property
    def size(self) -> int:
        return len(self.sigs)


@dataclass
class SlotRequest:
    """One duty of one slot: one aggregate+verify call, with what the
    reference needs to decide its answer."""

    index: int
    slot: int
    duty: str
    ids: tuple[int, ...]          # the share ids aggregated, as arrived
    pks: list[bytes]              # per validator: its group pubkey
    roots: list[bytes]            # per validator
    partials: list[dict[int, bytes]]   # per validator: share id -> partial
    secrets: list[int]            # per validator: its group secret
    shares: list[dict[int, int]]  # per validator: share id -> share secret
    bad: int = 0                  # validators with a partial over another root
    last_of_slot: bool = True

    @property
    def size(self) -> int:
        return len(self.partials)


class Cluster:
    """Seeded validator keys with `threshold`-of-`nodes` Shamir shares
    (share index i holds f(i), the group key is f(0))."""

    def __init__(self, rng: random.Random, validators: int, nodes: int,
                 threshold: int):
        self.nodes, self.threshold = nodes, threshold
        self.secrets: list[int] = []
        self.shares: list[dict[int, int]] = []
        for _ in range(validators):
            coeffs = [rng.randrange(1, ref.R) for _ in range(threshold)]
            self.secrets.append(coeffs[0])
            shares = {}
            for i in range(1, nodes + 1):
                acc = 0
                for c in reversed(coeffs):
                    acc = (acc * i + c) % ref.R
                shares[i] = acc
            self.shares.append(shares)

    @property
    def size(self) -> int:
        return len(self.secrets)


class Generator:
    """Makes a mix's requests for one configuration from one seed."""

    def __init__(self, config: dict, mix: dict, seed: int):
        self.config, self.mix = config, mix
        self.rng = random.Random(seed)
        self.cluster = Cluster(self.rng, config["validators"],
                               config["nodes"], config["threshold"])
        self.peers = [i for i in range(1, config["nodes"] + 1)
                      if i != config["node_share"]]
        per_epoch = int(mix["slots_per_epoch"])
        order = list(range(self.cluster.size))
        self.rng.shuffle(order)
        self.attesters = [sorted(order[s::per_epoch])
                          for s in range(per_epoch)]
        self.duties = list(mix["duties"])
        self.slot_entry = mix["entry"] == SLOT_ENTRY
        self._pks: dict[tuple[int, int], bytes] = {}
        self.slot = 0
        self.count = 0

    def _pk(self, peer: int, v: int) -> bytes:
        """Share `peer`'s pubkey of validator v; peer 0 is its group key."""
        key = (peer, v)
        if key not in self._pks:
            self._pks[key] = ref.pubkey(self.cluster.shares[v][peer] if peer
                                        else self.cluster.secrets[v])
        return self._pks[key]

    def _slot_duties(self, bad_sets: int, bad: int) -> list[SlotRequest]:
        """The next slot's aggregations, one request per duty; the first
        `bad_sets` have `bad` validators, in their second half, with one
        partial over another root."""
        slot, self.slot = self.slot, self.slot + 1
        vals = self.attesters[slot % len(self.attesters)]
        own = self.config["node_share"]
        out: list[SlotRequest] = []
        for duty in self.duties:
            root = self.rng.randbytes(32)
            order = list(self.peers)
            self.rng.shuffle(order)
            ids = tuple(sorted([own] + order[:self.cluster.threshold - 1]))
            shares = [{i: self.cluster.shares[v][i] for i in ids}
                      for v in vals]
            sigs = {i: ref.sign_many(root, [sh[i] for sh in shares])
                    for i in ids}
            out.append(SlotRequest(
                self.count, slot, duty, ids, [self._pk(0, v) for v in vals],
                [root] * len(vals),
                [{i: sigs[i][k] for i in ids} for k in range(len(vals))],
                [self.cluster.secrets[v] for v in vals], shares))
            self.count += 1
        for r in out[:bad_sets]:
            n = r.size
            where = self.rng.sample(range(n // 2, n), min(bad, n - n // 2))
            other = self.rng.randbytes(32)
            for k in where:
                i = self.rng.choice(r.ids)
                r.partials[k][i] = ref.sign(r.shares[k][i], other)
            r.bad = len(where)
        for r in out[:-1]:
            r.last_of_slot = False
        return out

    def next_slot(self, sets: int | None = None, bad_sets: int = 0,
                  bad: int = 0) -> list[Request]:
        """The next slot's sets, duty by duty, each duty's peers in a seeded
        order; only the first `sets` of them when given. The first
        `bad_sets` sets have `bad` partials over another root, in their
        second half."""
        slot, self.slot = self.slot, self.slot + 1
        vals = self.attesters[slot % len(self.attesters)]
        out: list[Request] = []
        for duty in self.duties:
            root = self.rng.randbytes(32)
            order = list(self.peers)
            self.rng.shuffle(order)
            for peer in order:
                secrets = [self.cluster.shares[v][peer] for v in vals]
                out.append(Request(
                    self.count, slot, duty, peer,
                    [self._pk(peer, v) for v in vals], [root] * len(vals),
                    ref.sign_many(root, secrets), secrets))
                self.count += 1
        if sets is not None:
            out = out[:sets]
        for r in out[:bad_sets]:
            n = r.size
            where = self.rng.sample(range(n // 2, n), min(bad, n - n // 2))
            other = self.rng.randbytes(32)
            for i, s in zip(where, ref.sign_many(
                    other, [r.secrets[i] for i in where])):
                r.sigs[i] = s
            r.bad = len(where)
        out[-1].last_of_slot = True
        return out

    def warm(self) -> list:
        mix = self.mix
        n, bad_sets = int(mix["warm_sets"]), int(mix["warm_bad_sets"])
        if not self.slot_entry:
            return self.next_slot(n, bad_sets, int(mix["bad_partials"]))
        return [r for k in range(n) for r in self._slot_duties(
            int(k < bad_sets), int(mix["bad_partials"]))]

    def pool(self) -> list:
        """Every request the window may send: `pool_slots` whole slots."""
        slots = range(int(self.mix["pool_slots"]))
        if self.slot_entry:
            return [r for _ in slots for r in self._slot_duties(0, 0)]
        return [r for _ in slots for r in self.next_slot()]
