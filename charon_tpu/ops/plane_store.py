"""Device-resident public-key plane store.

A DV cluster's pubkey sets are fixed between reconfigurations (the share⇄
root maps are built once from the cluster lock, reference app/app.go:
339-383), so every slot verifies against the SAME pubkeys. Before this
store, the chunked-verify path cached decompressed planes under per-chunk
CONTENT slices (`pks[s:e]`), so a >TILE burst churned the 12-entry LRU —
sized for whole per-peer sets — and re-paid the device decompress +
subgroup dispatch every slot (ADVICE round 5; the ISSUE-2 motivation).

Here every cached plane is keyed on the FULL-SET digest plus the chunk
span and bucket: `(sha256(pks), start, end, bucket)`. A chunked verify of
a fixed peer set decodes each chunk exactly once per process; every later
slot is pure cache hits, i.e. zero host→device decompress work in the
steady state. The per-chunk decode goes through the SAME already-compiled
≤TILE-lane graphs as before — the store never builds a plane wider than a
chunk bucket, so no new >TILE graph can compile (the remote compile
ceiling that forced chunking in the first place, plane_agg
rlc_verify_dispatch).

Pinning: the cluster's own sets (the sigagg root set, per-peer share
sets) can be pinned by full-set digest so cache pressure from transient
sets (e.g. one-off API verifies) can never evict them. Eviction is
LRU-with-refresh over UNPINNED entries only; if everything is pinned the
store grows past `max_entries` rather than dropping a pinned plane.

Counters (utils/metrics.py, printed by bench.py):
  ops_planestore_hits_total / misses_total   {kind="device"|"host"}
  ops_planestore_evictions_total
  ops_planestore_decompress_dispatches_total — device decode+subgroup
      dispatches issued; ZERO growth after slot 1 for a fixed peer set is
      the steady-state acceptance check
  ops_planestore_entries / pinned_sets / resident_bytes gauges

The decode entry point (`_decode_chunks`) resolves
`plane_agg.g1_plane_from_compressed` / `g1_subgroup_ok` late through the
module so tests can spy/stub them exactly like the previous cache did.
"""

from __future__ import annotations

import hashlib
import threading

from ..utils import metrics, tracer

_hits = metrics.counter(
    "ops_planestore_hits_total",
    "PlaneStore cache hits", ("kind",))
_misses = metrics.counter(
    "ops_planestore_misses_total",
    "PlaneStore cache misses", ("kind",))
_evictions = metrics.counter(
    "ops_planestore_evictions_total",
    "PlaneStore LRU evictions")
_decompress = metrics.counter(
    "ops_planestore_decompress_dispatches_total",
    "Device decompress+subgroup dispatches issued by the PlaneStore")
_entries_g = metrics.gauge(
    "ops_planestore_entries", "Resident PlaneStore entries")
_pinned_g = metrics.gauge(
    "ops_planestore_pinned_sets", "Pinned full-set digests")
_bytes_g = metrics.gauge(
    "ops_planestore_resident_bytes",
    "Device bytes held by resident planes")


def _entry_nbytes(entry) -> int:
    """Best-effort device-byte accounting: PlanePoint grew an `nbytes`
    property; host entries are tuples of numpy arrays; test stubs have
    neither and count as 0."""
    n = getattr(entry, "nbytes", None)
    if isinstance(n, int):
        return n
    if isinstance(entry, tuple):
        return sum(_entry_nbytes(e) for e in entry)
    return 0


class PlaneStore:
    """Device-resident cache of decoded pubkey planes, keyed on
    (full-set digest, chunk span, bucket) with pinning (module doc)."""

    def __init__(self, max_entries: int = 64):
        # sized for num_peers share-pubkey sets × a few chunks each plus
        # the sigagg root set of the largest supported cluster; per-CHUNK
        # entries, so the cap is a multiple of the old 12-full-set LRU
        self.max_entries = max_entries
        self._lock = threading.RLock()
        self._entries: dict[tuple, object] = {}  # insertion order = LRU
        self._pinned: set[bytes] = set()

    # ---- keying ----------------------------------------------------------

    @staticmethod
    def digest(pks) -> bytes:
        """Content digest of the FULL pubkey set — the stable half of every
        key (chunk spans vary; the set identity does not)."""
        h = hashlib.sha256()
        for p in pks:
            h.update(bytes(p))
        return h.digest()

    # ---- pinning ---------------------------------------------------------

    def pin(self, pks) -> None:
        """Mark a full set as evict-proof (the cluster's own share/root
        sets). Pins the digest, not the entries: chunks decoded later under
        this set are protected too."""
        if not pks:
            return
        with self._lock:
            self._pinned.add(self.digest(pks))
            _pinned_g.set(len(self._pinned))

    def unpin(self, pks) -> None:
        with self._lock:
            self._pinned.discard(self.digest(pks))
            _pinned_g.set(len(self._pinned))

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._pinned.clear()
            self._update_gauges()

    # ---- device planes ---------------------------------------------------

    def full_plane(self, pks: list, Bp: int):
        """Whole-set plane at bucket Bp — the single-chunk case (the fused
        sigagg path and the non-device verify path)."""
        return self.chunk_planes(pks, [(0, len(pks))], [Bp])[0]

    def chunk_planes(self, pks: list, chunks: list[tuple[int, int]],
                     buckets: list[int] | None = None) -> list:
        """Planes for `chunks` spans of the full set `pks`, decoded at most
        once per (span, bucket) per process. Raises ValueError (and caches
        nothing for the failing chunk) on an invalid/∞/out-of-subgroup
        pubkey, like the plane loaders."""
        from . import plane_agg

        if buckets is None:
            buckets = [plane_agg._bucket(e - s) for s, e in chunks]
        dg = self.digest(pks)
        with self._lock:
            out: list = [None] * len(chunks)
            missing: list[tuple[int, int, int, int]] = []
            for i, ((s, e), Bc) in enumerate(zip(chunks, buckets)):
                key = (dg, s, e, Bc)
                plane = self._entries.get(key)
                if plane is None:
                    missing.append((i, s, e, Bc))
                else:
                    # true LRU: refresh on hit so a working set larger than
                    # insertion order suggests keeps its hottest entries
                    self._entries.pop(key)
                    self._entries[key] = plane
                    _hits.inc("device")
                    out[i] = plane
            for i, s, e, Bc in missing:
                _misses.inc("device")
            tracer.set_attrs(misses=len(missing))
            if missing:
                for (i, s, e, Bc), plane in zip(
                        missing, self._decode_chunks(pks, missing)):
                    self._insert((dg, s, e, Bc), plane)
                    out[i] = plane
            return out

    def _decode_chunks(self, pks: list, missing) -> list:
        """THE bulk-uncompress entry point: decode + subgroup-check each
        missing chunk through the already-compiled ≤TILE-lane loaders
        (late-bound via plane_agg so tests can spy). One decompress
        dispatch is counted per chunk — the quantity bench.py asserts
        stays flat across warm slots."""
        from . import plane_agg

        planes = []
        with tracer.start_span("ops/planestore/decode_chunks",
                               chunks=len(missing)) as span:
            for _i, s, e, Bc in missing:
                _decompress.inc()
                span.add_event("decompress_dispatch", start=s, end=e)
                plane = plane_agg.g1_plane_from_compressed(
                    [bytes(p) for p in pks[s:e]], Bc, reject_infinity=True)
                if not plane_agg.g1_subgroup_ok(plane):
                    raise ValueError("G1 pubkey not in subgroup")
                planes.append(plane)
        return planes

    # ---- host-side entries -----------------------------------------------

    def host_entry(self, pks: list, extra_key: tuple, build):
        """Memoize a HOST-side derivation of a pubkey set, same digest
        keying and LRU as the device planes. `build()` runs under the
        store lock on miss. (The sharded pk stacks moved to sharded_entry
        below — device-resident, not host.)"""
        key = (self.digest(pks), "host") + tuple(extra_key)
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                self._entries.pop(key)
                self._entries[key] = entry
                _hits.inc("host")
                return entry
            _misses.inc("host")
            entry = build()
            self._insert(key, entry)
            return entry

    # ---- sharded device entries (multi-device sigagg) --------------------

    def sharded_entry(self, pks: list, geometry: tuple, build):
        """Memoize a DEVICE-RESIDENT sharded derivation of a pubkey set —
        the sharded plane's per-device pk parse stacks, placed with a
        NamedSharding across the mesh by `build()`. Keyed on the full-set
        digest plus the caller's shard geometry — (D, Vd, Vp) on one
        host, (W, Vd, Vp, hosts, host_index) on a multi-host topology
        (the host_index keeps two hosts' DIFFERENT chunk ranges from
        colliding, and preserves the exact single-host key when hosts is
        1) — so a mesh-width, bucket or membership change builds a fresh
        placement while the steady state (static cluster set, fixed mesh)
        is pure hits: zero host parse AND zero host→device pk transfer
        per slot. Same LRU/pinning as the device planes; counted under
        kind="device". Tests that rebuild the mesh between cases must
        also swap in a fresh STORE — a cached entry holds arrays
        committed to the old mesh's devices.
        """
        key = (self.digest(pks), "sharded") + tuple(geometry)
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                self._entries.pop(key)
                self._entries[key] = entry
                _hits.inc("device")
                return entry
            _misses.inc("device")
            entry = build()
            self._insert(key, entry)
            return entry

    # ---- internals -------------------------------------------------------

    def _insert(self, key: tuple, entry) -> None:
        self._entries[key] = entry
        while len(self._entries) > self.max_entries:
            victim = next((k for k in self._entries
                           if k[0] not in self._pinned), None)
            if victim is None:
                break  # everything pinned: grow rather than drop a pin
            self._entries.pop(victim)
            _evictions.inc()
        self._update_gauges()

    def _update_gauges(self) -> None:
        _entries_g.set(len(self._entries))
        _pinned_g.set(len(self._pinned))
        _bytes_g.set(sum(_entry_nbytes(e) for e in self._entries.values()))

    def stats(self) -> dict[str, int]:
        """Flat counter/gauge snapshot for bench printing and tests."""
        with self._lock:
            return {
                "hits": int(_hits.value("device") + _hits.value("host")),
                "misses": int(_misses.value("device")
                              + _misses.value("host")),
                "evictions": int(_evictions.value()),
                "decompress_dispatches": int(_decompress.value()),
                "entries": len(self._entries),
                "pinned_sets": len(self._pinned),
                "resident_bytes": int(
                    sum(_entry_nbytes(e) for e in self._entries.values())),
            }


# Process-wide store: one device, one resident working set — mirroring the
# process-wide compile and plane caches it replaces. Tests swap in a fresh
# instance (monkeypatch.setattr(plane_store, "STORE", PlaneStore())).
STORE = PlaneStore()
