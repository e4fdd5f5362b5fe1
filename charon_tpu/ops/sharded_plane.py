"""Multi-chip (and multi-HOST) sharding of the PRODUCTION crypto plane.

The single-chip fused sigagg path (ops/plane_agg.threshold_aggregate_and_
verify) data-parallelizes over a `jax.sharding.Mesh` axis "data": validators
are split into contiguous chunks, one per device, and every device runs the SAME
fused pipeline the bench drives — batched G2 decompression, the windowed
Lagrange sweep + per-validator combine, the device affine serialization
front-half, and its slice of the RLC MSMs — entirely on local data (zero
communication). The only collective is the RLC combine: an EC-add
all-reduce of the per-device MSM partial sums (point addition is the
reduction operator, which psum cannot express) via a recursive-doubling
ppermute butterfly — log2(D) neighbor exchanges, one unified-add kernel
per round — exactly once per verify. The host then finishes with the
shared multi-pairing, as on one chip.

This replaces the reference's single-process herumi hot loop (reference
tbls/herumi.go:244-301, core/sigagg/sigagg.go:144-159) with a design that
scales over ICI: per-chip work is embarrassingly parallel, the single
all_gather moves E·LIMBS·TW ints per chip, and every kernel is the
identical pallas plane kernel the single-chip path uses.

Multi-host operation (ops/mesh.py resolves the topology) threads a
:class:`HostPlan` through the three stages. Validators chunk over the
CLUSTER width W = hosts × per-host width; each host packs, dispatches and
reads back ONLY its own contiguous chunk range (its addressable shards).
Two modes:

  * ``"global"`` (accelerators): the Mesh spans every host's devices, so
    the EC-add butterfly and the verify all_gather above run over the
    global mesh unchanged — the reduced sums come back replicated on
    every host and only the emitted aggregate bytes (plus a validity
    flag) cross the HostLink at finish.
  * ``"bridged"`` (XLA:CPU, which cannot execute multiprocess
    computations): each host reduces over its LOCAL mesh and the
    per-host partial sums cross the HostLink as raw limb planes; the
    cross-host EC combine is one extra lane-concatenated `_host_fold`,
    and the cluster verify exchanges per-chunk Fq12 products that fold
    IN-GRAPH through the single-final-exp finish
    (pairing.fold_chunks_is_one) — identical verdicts on every host.

A global device fence (HostLink barrier keyed by the slot's dispatch-
assigned sequence number) separates execute from drain, so no host races
ahead of a peer's in-flight device work and a dead peer surfaces as one
classified barrier timeout that rides the guard ladder.

Production entry: the module is split along the SAME three-stage seam as
plane_agg — `sharded_dispatch` (host pack + async dispatch, the "pack"
phase), `sharded_readback` (device fence + per-shard transfer, "execute"/
"drain") and the pure-host `sharded_host_finish` ("finish") — so
SigAggPipeline double-buffers and overlaps sharded slots exactly as it
does single-device ones. plane_agg routes every pipeline/batch entry here
whenever ops.mesh.sigagg_mesh() reports >1 device; the classic
`threshold_aggregate_and_verify_sharded` wrapper (dryrun/tests) is now a
thin dispatch+finish composition over the same stages. Also used by
__graft_entry__.dryrun_multichip (driver contract) and
tests/test_multichip.py; numerically cross-checked against the single-chip
path (bit-identical aggregate bytes, identical RLC decision).
"""

from __future__ import annotations

import dataclasses
import functools
import threading

import numpy as np

import jax
import jax.numpy as jnp

from ..utils import faults, metrics, tracer
from . import pallas_plane as PP
from . import plane_agg as PA

# Per-shard latency inside one sharded slot: "pack" is one device chunk's
# host parse, "transfer" is one shard's drain-side readback. The spread
# across shards (p99 vs p50) is the load-imbalance signal the benches
# print — contiguous chunking gives the LAST device the short remainder
# chunk, so a wide spread means V is too small for the mesh.
_shard_hist = metrics.histogram(
    "ops_sigagg_shard_seconds",
    "Per-shard phases of a sharded sigagg slot: host chunk pack, "
    "per-shard readback transfer", ("phase",),
    buckets=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
             0.5, 1, 2.5, 5))


@dataclasses.dataclass(frozen=True)
class HostPlan:
    """The multi-host coordinates of ONE slot, frozen at dispatch.

    Threaded through the state tuple into readback/finish/verify so every
    cross-host exchange of the slot — the device fence, the finish
    payload, the verify fold — keys on the SAME dispatch-assigned
    sequence number regardless of which pipeline worker thread runs the
    stage (stage-3 workers race; exchange tags must not depend on call
    order). hosts == 1 is the single-host passthrough: no link, no
    exchanges, byte-for-byte the pre-multi-host behaviour."""

    hosts: int
    host_index: int
    mode: str        # "local" | "bridged" | "global"
    seq: int
    link: object     # mesh.HostLink when hosts > 1


_LOCAL_PLAN = HostPlan(1, 0, "local", 0, None)

_seq_lock = threading.Lock()
_seq_state: list = [None, 0]  # [link identity, next slot sequence]


def _next_seq(link) -> int:
    """Dispatch-order slot sequence, scoped to one HostLink (a rebuilt
    link — new membership epoch — restarts at 0 on every host together).
    Dispatch runs in SPMD submission order under the pipeline lock, so
    the counters advance in lockstep across hosts."""
    with _seq_lock:
        if _seq_state[0] is not link:
            _seq_state[0] = link
            _seq_state[1] = 0
        seq = _seq_state[1]
        _seq_state[1] += 1
        return seq


def _host_plan(mesh) -> HostPlan:
    """The HostPlan for a slot dispatched over `mesh` right now. A
    narrowed guard-ladder rung on a multi-host cluster is a LOCAL mesh,
    so it plans bridged mode even where the primary mesh is global —
    per-host width narrows while the cluster combine stays on the
    HostLink."""
    from . import mesh as mesh_mod

    if mesh_mod.host_count() <= 1:
        return _LOCAL_PLAN
    link = mesh_mod.host_link()
    if link is None:
        return _LOCAL_PLAN
    mode = "global" if mesh_mod.is_global_mesh(mesh) else "bridged"
    return HostPlan(mesh_mod.host_count(), mesh_mod.host_index(), mode,
                    _next_seq(link), link)


def _plan_width(mesh, plan) -> int:
    """PER-HOST shard width under `plan` (the global mesh carries every
    host's devices; bridged/local meshes are already host-local)."""
    D = mesh.devices.size
    if plan.mode == "global" and plan.hosts > 1:
        return D // plan.hosts
    return D


def _chunk_plane_inputs(batches, Vp: int, T: int):
    """Host-side parse of one device's validator chunk into raw-limb planes
    — the exact permuted T-slot layout the single-chip path builds
    (plane_agg._layout_slots with the globally-fixed Vp/T)."""
    sigs_all, scalars_all, _V, _Vp, _T, _Wv = PA._layout_slots(
        batches, Vp=Vp, T=T)
    body, _fin, sgn, loaded = PA._parse_compressed(
        sigs_all, 96, "G2", False, Vp * T)
    X0r = PA._raw_to_plane(body[:, 48:], Vp * T)
    X1r = PA._raw_to_plane(body[:, :48], Vp * T)
    digits = PP.scalars_to_digitplanes(scalars_all, Vp * T)
    return X0r, X1r, sgn, loaded, digits


def _fold_gathered(gX, gY, gZ, E):
    """Unified-EC-add fold of an all_gather'd (D, E, LIMBS, S, W) stack —
    log2(D) rounds of the same fused add kernel, inside the sharded jit."""
    parts = [(gX[d], gY[d], gZ[d]) for d in range(gX.shape[0])]
    while len(parts) > 1:
        nxt = []
        for k in range(0, len(parts) - 1, 2):
            nxt.append(PP._add_call(*parts[k], *parts[k + 1], E))
        if len(parts) % 2:
            nxt.append(parts[-1])
        parts = nxt
    return parts[0]


@functools.lru_cache(maxsize=8)
def _build_steps(mesh, G: int, T: int, Wv: int):
    """The three sharded jits of the pipeline, cached per (mesh, shape
    family) so repeated slots reuse the in-memory compiled executables —
    (1) decompress + sweep + affine, (2) local MSMs, (3) the EC-add
    all-reduce. Split three ways because XLA's compile time is superlinear
    in graph size and the pieces compile (and persistent-cache)
    independently; intermediates stay sharded on the devices between them.
    On a multi-host GLOBAL mesh, D below is the cluster width and the
    step-3 butterfly's neighbor exchanges span hosts over ICI/DCN; on a
    bridged mesh D is the host-local width and step 3 produces per-host
    partial sums the finish stage combines over the HostLink.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    D = mesh.devices.size

    def _local_agg(X0r, X1r, sgn, lmask, digits, pkXr, pk_sgn, pk_lmask):
        # each operand arrives with a leading local-device axis of size 1
        X, Y, Z, ok = PA._g2_decompress_jit(
            X0r[0], X1r[0], sgn[0], lmask[0])
        RX, RY, RZ = PA._sweep_combine_jit(X, Y, Z, digits[0], T, Wv)
        xs, sign, inf = PA._g2_affine_std_jit(RX, RY, RZ)
        pX, pY, pZ, pok = PA._g1_decompress_jit(pkXr[0], pk_sgn[0],
                                                pk_lmask[0])
        return (ok[None], pok[None], xs[None], sign[None], inf[None],
                RX[None], RY[None], RZ[None], pX[None], pY[None], pZ[None])

    def _local_msm(RX, RY, RZ, pX, pY, pZ, rdig, gmask):
        # sig-G2 + pk-G1 MSMs through ONE windowed sweep (the same Fq2
        # embedding the single-chip _combined_msm uses); the reduced
        # per-device sums stay SHARDED — the cross-chip combine is its own
        # small graph (_gather_fold below)
        sig_red, pk_local = PA._combined_msm(
            RX[0], RY[0], RZ[0], pX[0], pY[0], pZ[0], rdig[0], gmask[0], G)
        PX = jnp.stack([pk_local[g][0] for g in range(G)])
        PY = jnp.stack([pk_local[g][1] for g in range(G)])
        PZ = jnp.stack([pk_local[g][2] for g in range(G)])
        return (sig_red[0][None], sig_red[1][None], sig_red[2][None],
                PX[None], PY[None], PZ[None])

    def _gather_fold(sX, sY, sZ, pX, pY, pZ):
        # the ONLY collective of the pipeline: an EC-add ALL-REDUCE of the
        # per-device RLC partial sums over "data" (point addition is the
        # reduction operator, which psum cannot express). Recursive-doubling
        # butterfly: log2(D) rounds of ppermute + ONE unified add, with the
        # sig plane and the G pk-group planes CONCATENATED on the lane axis
        # so every round is a single kernel — arrays stay per-device sized
        # (no D-wide gathered intermediate), rounds ride neighbor exchanges
        # on a real ICI mesh, and the graph is ~5x smaller to compile than
        # the all_gather+fold it replaces (379 s → tens of s on the
        # 1-core XLA:CPU dryrun host). Kept as its own jit: XLA's compile
        # time is superlinear in graph size.
        W = sX.shape[-1]
        CX = jnp.concatenate([sX[0]] + [pX[0, g] for g in range(G)], axis=-1)
        CY = jnp.concatenate([sY[0]] + [pY[0, g] for g in range(G)], axis=-1)
        CZ = jnp.concatenate([sZ[0]] + [pZ[0, g] for g in range(G)], axis=-1)
        if D & (D - 1):
            # non-power-of-two mesh: XOR pairing doesn't cover it — fall
            # back to gather + pairwise fold (same result, bigger graph)
            CX, CY, CZ = _fold_gathered(
                jax.lax.all_gather(CX, "data"),
                jax.lax.all_gather(CY, "data"),
                jax.lax.all_gather(CZ, "data"), 2)
        else:
            k = 1
            while k < D:
                perm = [(i, i ^ k) for i in range(D)]
                RX = jax.lax.ppermute(CX, "data", perm)
                RY = jax.lax.ppermute(CY, "data", perm)
                RZ = jax.lax.ppermute(CZ, "data", perm)
                CX, CY, CZ = PP._add_call(CX, CY, CZ, RX, RY, RZ, 2)
                k *= 2
        SX, SY, SZ = CX[..., :W], CY[..., :W], CZ[..., :W]
        PX = jnp.stack([CX[..., (g + 1) * W:(g + 2) * W] for g in range(G)])
        PY = jnp.stack([CY[..., (g + 1) * W:(g + 2) * W] for g in range(G)])
        PZ = jnp.stack([CZ[..., (g + 1) * W:(g + 2) * W] for g in range(G)])
        return SX, SY, SZ, PX, PY, PZ
    spec_d = P("data")
    step1 = jax.jit(shard_map(
        _local_agg, mesh=mesh,
        in_specs=(spec_d,) * 8,
        out_specs=(spec_d,) * 11,
        check_vma=False,
    ))
    step2 = jax.jit(shard_map(
        _local_msm, mesh=mesh,
        in_specs=(spec_d,) * 8,
        out_specs=(spec_d,) * 6,
        check_vma=False,
    ))
    step3 = jax.jit(shard_map(
        _gather_fold, mesh=mesh,
        in_specs=(spec_d,) * 6,
        out_specs=(P(),) * 6,  # the all-reduce leaves the sums replicated
        check_vma=False,
    ))
    return step1, step2, step3


def _placer(mesh, plan):
    """Placement function for dispatch operands: plain device_put with the
    "data" NamedSharding on a host-local mesh; on a multi-host GLOBAL mesh
    each host contributes only its D local rows and
    `jax.make_array_from_process_local_data` assembles the W-row global
    array without any cross-host data movement (the rows are already
    where they belong — placement-correct by construction)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    shard = NamedSharding(mesh, P("data"))
    if plan.mode == "global" and plan.hosts > 1:
        def place(a):
            a = np.asarray(a)
            return jax.make_array_from_process_local_data(
                shard, a, (a.shape[0] * plan.hosts,) + a.shape[1:])
        return place
    return lambda a: jax.device_put(jnp.asarray(a), shard)


def sharded_dispatch(batches, pks, msgs, mesh, rs=None, plan=None):
    """Stage 1 of a sharded slot: host pack + async dispatch over mesh
    axis "data"; returns the pending state plane_agg._fused_readback /
    _fused_host_finish (and with them SigAggPipeline) complete. Same
    contract and trust preconditions as plane_agg._fused_dispatch —
    everything here is host work + enqueue (the "pack" phase of
    ops_device_dispatch_seconds); NOTHING syncs on the device or the
    HostLink, so the pipeline lock may cover this whole body
    (LINT-TPU-007). On a multi-host topology (`plan` defaults to the
    resolved ops.mesh one) this host packs ONLY its own chunk range.

    Pubkey validation — infinity rejection + subgroup membership, which
    RLC soundness requires — runs through plane_agg.validate_pk_set:
    once per distinct pubkey set per process (a cluster's validator set
    is static between reconfigurations), not per slot, and via the
    NATIVE backend so no single-device graph compiles inside the
    multichip dryrun (the _pk_plane_cached route cold-compiled
    _g1_subgroup_jit for ~6 min on the driver host — MULTICHIP_r04.json
    rc=124). An invalid/∞/out-of-subgroup pubkey degrades to the
    "sharded_bad_pk" state — aggregates still computed, all_valid=False
    at finish — bit-identical to the single-device bad_pk contract (and
    identical on every host: the full set is validated everywhere)."""
    V = len(batches)
    if not (V == len(pks) == len(msgs)):
        raise ValueError("length mismatch")
    if V == 0:
        return ("sharded_empty",)
    if plan is None:
        plan = _host_plan(mesh)
    D = _plan_width(mesh, plan)
    with tracer.start_span("ops/sharded_dispatch", validators=V,
                           shards=D, hosts=plan.hosts) as span, \
            PA._dispatch_hist.observe_time("pack"):
        faults.check("sigagg.pack")
        try:
            PA.validate_pk_set([bytes(p) for p in pks])
        except ValueError:
            span.attrs["outcome"] = "sharded_bad_pk"
            return ("sharded_bad_pk", [dict(b) for b in batches])
        state = _sharded_dispatch_impl(batches, pks, msgs, mesh, rs, span,
                                       plan)
        span.attrs["outcome"] = state[0]
        PA._shard_width.set(float(D))
        PA._host_shard_width.set(float(D), str(plan.host_index))
        return state


def _sharded_dispatch_impl(batches, pks, msgs, mesh, rs, span, plan):
    V = len(batches)
    D = _plan_width(mesh, plan)    # per-host shard width
    W = D * plan.hosts             # cluster-wide chunk count
    h = plan.host_index
    T = max(len(b) for b in batches)
    if T == 0:
        raise ValueError("empty partial signature set")
    Vd = -(-V // W)          # validators per device, cluster-wide
    Vp = PA._bucket_for_slots(Vd, T)   # padded per-device plane (T-slot
    #                                    combined width must be a bucket)
    Wv = Vp // PP.SUB

    # ---- host-side parse, one chunk per LOCAL device (timed per shard);
    # global chunk c = h·D + d, so every host owns a contiguous validator
    # range and host-ordered concatenation restores global order ---------
    stacks = []
    for d in range(D):
        c = h * D + d
        with _shard_hist.observe_time("pack"):
            stacks.append(_chunk_plane_inputs(
                batches[c * Vd:(c + 1) * Vd], Vp, T))
        span.add_event("shard_pack", shard=c)
    X0r, X1r, sgn, lmask, digits = (np.stack(a) for a in zip(*stacks))

    # the per-device pk parse stacks are a pure function of the (static)
    # pubkey set and the shard geometry — built once per (digest,
    # geometry) and held DEVICE-RESIDENT with NamedSharding placement in
    # the PlaneStore, so steady-state slots skip both the whole-set byte
    # parse and the host→device transfer of the pk planes. The geometry
    # key keeps the exact single-host shape when hosts == 1 (bit-stable
    # cache reuse) and adds (hosts, host_index) otherwise.
    place = _placer(mesh, plan)

    def _parse_pk_chunks():
        pk_chunks = [PA._parse_compressed(
            [bytes(p) for p in pks[(h * D + d) * Vd:(h * D + d + 1) * Vd]]
            or [b"\xc0" + bytes(47)],
            48, "G1", False, Vp) for d in range(D)]
        host = (np.stack([PA._raw_to_plane(pc[0], Vp) for pc in pk_chunks]),
                np.stack([pc[2] for pc in pk_chunks]),
                np.stack([pc[3] for pc in pk_chunks]))
        return tuple(place(a) for a in host)

    from . import plane_store

    geometry = ((D, Vd, Vp) if plan.hosts == 1
                else (W, Vd, Vp, plan.hosts, plan.host_index))
    pkXr, pk_sgn, pk_lmask = plane_store.STORE.sharded_entry(
        [bytes(p) for p in pks], geometry, _parse_pk_chunks)

    # RLC randomizers: per validator, chunked per device; padding lanes
    # carry zero (infinity contributions). Hosts need NO cross-host
    # agreement on rs — validator i's rᵢ weights both its signature and
    # its pubkey side, and both live on i's owner host.
    if rs is None:
        rs = PA.sample_randomizers(V)
    rdig = np.stack([
        PP.scalars_to_digitplanes(
            rs[(h * D + d) * Vd:(h * D + d + 1) * Vd], Vp,
            nbits=PA.RLC_BITS)
        for d in range(D)])

    # distinct-message groups (global, static per compile, padded to a
    # power of two with empty groups like plane_agg._group_masks so the
    # sharded graph specializes on O(log) G values); the mask is built
    # over the CLUSTER chunk axis then sliced to this host's rows
    groups: dict[bytes, list[int]] = {}
    for i, m in enumerate(msgs):
        groups.setdefault(bytes(m), []).append(i)
    G = 1
    while G < len(groups):
        G *= 2
    group_keys = list(groups.keys()) + [b""] * (G - len(groups))
    gmask = np.zeros((W, G, PP.SUB, Vp // PP.SUB), bool)
    for g, idxs in enumerate(groups.values()):
        for i in idxs:
            c, loc = i // Vd, i % Vd
            gmask[c, g, loc // (Vp // PP.SUB), loc % (Vp // PP.SUB)] = True
    gmask = gmask[h * D:(h + 1) * D]

    step1, step2, step3 = _build_steps(mesh, G, T, Wv)
    a1 = [place(a) for a in (X0r, X1r, sgn, lmask, digits)]
    (ok, pok, xs, sign, inf,
     RXs, RYs, RZs, pXs, pYs, pZs) = step1(*a1, pkXr, pk_sgn, pk_lmask)
    a2 = [place(a) for a in (rdig, gmask)]
    SX, SY, SZ, PX, PY, PZ = step3(*step2(RXs, RYs, RZs, pXs, pYs, pZs, *a2))
    return ("sharded_pending", V, D, Vd, group_keys,
            (ok, pok, xs, sign, inf), (SX, SY, SZ, PX, PY, PZ), plan)


def _shards_by_index(arr, D, offset: int = 0):
    """One addressable shard per LOCAL mesh position along axis 0, ordered
    by global index (minus `offset`, the first row this host owns on a
    global mesh), or None when the layout is not the expected 1-D "data"
    sharding (callers fall back to a wholesale device_get)."""
    try:
        shards = list(arr.addressable_shards)
        if len(shards) != D:
            return None
        parts = [None] * D
        for s in shards:
            idx = s.index[0].start if s.index else None
            if idx is not None:
                idx -= offset
            if idx is None or not 0 <= idx < D or parts[idx] is not None:
                return None
            parts[idx] = s
        return parts
    except Exception:  # noqa: BLE001 — unexpected layout: fall back
        return None


def sharded_readback(state, span=None):
    """Stage 2→3 boundary of a sharded slot: block on the mesh-wide work
    ("execute" phase) then transfer results shard by shard ("drain") so
    each device's readback is individually timed (ops_sigagg_shard_seconds
    {phase="transfer"} + shard_transfer span events). On a multi-host
    topology the local fence is followed by the GLOBAL device fence — a
    HostLink barrier keyed by the slot's sequence number — so no host
    drains before every host's device work is done, and a dead peer
    surfaces here as one classified barrier timeout that rides the guard
    ladder. Each host transfers ONLY its addressable shards.
    "sharded_bad_pk"/"sharded_empty" states pass through untouched."""
    if state[0] in ("sharded_bad_pk", "sharded_empty"):
        if span is not None:
            span.attrs["outcome"] = state[0]
        return state
    _tag, V, D, Vd, group_keys, shard_outs, red_outs, plan = state
    with PA._dispatch_hist.observe_time("execute"):
        jax.block_until_ready(shard_outs)
        jax.block_until_ready(red_outs)
        if plan.hosts > 1 and plan.link is not None:
            plan.link.barrier(f"slot/{plan.seq}/fence")
    if span is not None:
        span.add_event("device_fence")
    faults.check("sigagg.readback")
    offset = plan.host_index * D if plan.mode == "global" else 0
    with PA._dispatch_hist.observe_time("drain"):
        per = [_shards_by_index(a, D, offset) for a in shard_outs]
        if all(p is not None for p in per):
            cols = [[None] * D for _ in shard_outs]
            for d in range(D):
                with _shard_hist.observe_time("transfer"):
                    for i in range(len(shard_outs)):
                        cols[i][d] = np.asarray(per[i][d].data)
                if span is not None:
                    span.add_event("shard_transfer", shard=offset + d)
            host_shards = tuple(np.concatenate(c, axis=0) for c in cols)
        elif plan.mode == "global" and plan.hosts > 1:
            # a global array we cannot read shard-by-shard is a topology
            # change mid-slot — let the guard ladder re-resolve
            raise RuntimeError("unexpected shard layout on global mesh")
        else:
            host_shards = tuple(np.asarray(a)
                                for a in jax.device_get(shard_outs))
        host_reds = tuple(np.asarray(a) for a in jax.device_get(red_outs))
    return ("sharded_host", V, D, Vd, group_keys, host_shards, host_reds,
            plan)


def sharded_host_finish(hstate, hash_fn=None):
    """Stage 3, blocking shape: emit half + immediate verify (see
    sharded_host_emit) — the guard ladder / serial callers' seam."""
    out, verify = sharded_host_emit(hstate, hash_fn)
    return out, verify()


def _cat_lanes(arrs):
    """Stack per-host partial-sum planes on the fold lane axis: each host
    ships (E, LIMBS, ...) limb planes; reshaping to (E, LIMBS, lanes) and
    concatenating makes the cross-host EC combine ONE extra `_host_fold`
    over hosts × lanes points — same group element as a global-mesh
    reduction (fold order changes the Jacobian representative, never the
    point, and the emitted aggregate bytes are per-validator anyway)."""
    return np.concatenate(
        [np.asarray(a).reshape(a.shape[0], a.shape[1], -1) for a in arrs],
        axis=-1)


def _exchange_finish(out_local, valid, host_reds, group_keys, plan):
    """The finish-stage HostLink exchange: every host publishes its
    validity flag + emitted aggregate bytes (and, in bridged mode, its
    per-host RLC partial-sum planes) under the slot's sequence tag, and
    reconstructs the CLUSTER result — host-ordered aggregate bytes, the
    folded S = Σ rᵢ·sigᵢ and per-group P_m points. Raises the same
    "invalid point" ValueError as the local path when ANY host saw an
    invalid point, so all hosts take the same error path."""
    from . import mesh as mesh_mod

    payload = {"valid": np.asarray([1 if valid else 0], np.uint8),
               "emit": np.frombuffer(b"".join(out_local), np.uint8)}
    if plan.mode != "global":
        SX, SY, SZ, PX, PY, PZ = host_reds
        payload.update(
            sx=np.asarray(SX), sy=np.asarray(SY), sz=np.asarray(SZ),
            px=np.asarray(PX), py=np.asarray(PY), pz=np.asarray(PZ))
    blobs = plan.link.exchange(f"slot/{plan.seq}/finish",
                               mesh_mod.pack_arrays(**payload))
    decoded = [mesh_mod.unpack_arrays(b) for b in blobs]
    if not all(int(d["valid"][0]) for d in decoded):
        raise ValueError("invalid point in sharded load")
    out: list[bytes] = []
    for d in decoded:
        blob = d["emit"].tobytes()
        out.extend(blob[i * 96:(i + 1) * 96]
                   for i in range(len(blob) // 96))
    if plan.mode == "global":
        # the in-graph butterfly already spanned hosts — the reduced sums
        # came back replicated; only the bytes needed exchanging
        SX, SY, SZ, PX, PY, PZ = host_reds
        S = PP._host_fold(SX, SY, SZ, 2)
        pts = [(m, PA._unembed_g1(PP._host_fold(PX[g], PY[g], PZ[g], 2)))
               for g, m in enumerate(group_keys)]
        return out, S, pts
    S = PP._host_fold(_cat_lanes([d["sx"] for d in decoded]),
                      _cat_lanes([d["sy"] for d in decoded]),
                      _cat_lanes([d["sz"] for d in decoded]), 2)
    pts = [(m, PA._unembed_g1(PP._host_fold(
        _cat_lanes([d["px"][g] for d in decoded]),
        _cat_lanes([d["py"][g] for d in decoded]),
        _cat_lanes([d["pz"][g] for d in decoded]), 2)))
        for g, m in enumerate(group_keys)]
    return out, S, pts


def sharded_host_emit(hstate, hash_fn=None):
    """Stage 3, emit half — validity check, per-chunk byte emission and
    RLC host folds (the "finish" phase). Returns (aggregates,
    verify_thunk); the thunk runs the slot's pairing verification through
    PA._pairing_finish (the separately-timed "verify" phase, itself
    sharded over the mesh via sharded_pairing_check when one is up, with
    the slot's HostPlan threaded through so a multi-host verify exchanges
    under the SAME sequence tag). The heavy parts release the GIL so the
    pipeline's stage-3 workers overlap both halves with the next slot's
    pack and the in-flight execute. bad_pk degrades exactly like the
    single-device path: aggregates computed, all_valid=False."""
    if hstate[0] == "sharded_empty":
        return [], lambda: True
    if hstate[0] == "sharded_bad_pk":
        layout = PA._layout_slots(hstate[1])
        RX, RY, RZ, V, Vp = PA._aggregate_plane(None, layout)
        return PA._serialize_aggregates(RX, RY, RZ, V), lambda: False
    _tag, V, D, Vd, group_keys, host_shards, host_reds, plan = hstate
    with PA._dispatch_hist.observe_time("finish"):
        ok, pok, xs, sign, inf = host_shards
        valid = bool(ok.all() and pok.all())
        out: list[bytes] = []
        if valid:
            for d in range(D):
                c = plan.host_index * D + d
                n_local = min(Vd, max(0, V - c * Vd))
                if n_local:
                    out.extend(PA._g2_emit_bytes(
                        xs[d], sign[d].reshape(-1), inf[d].reshape(-1),
                        n_local))
        if plan.hosts > 1 and plan.link is not None:
            out, S, pts = _exchange_finish(out, valid, host_reds,
                                           group_keys, plan)
        else:
            if not valid:
                raise ValueError("invalid point in sharded load")
            SX, SY, SZ, PX, PY, PZ = host_reds
            S = PP._host_fold(SX, SY, SZ, 2)
            pts = [(m, PA._unembed_g1(PP._host_fold(PX[g], PY[g], PZ[g],
                                                    2)))
                   for g, m in enumerate(group_keys)]
    # _pairing_finish times itself as the "verify" phase — kept out of the
    # "finish" window so the two stay separately attributable
    return out, lambda: PA._pairing_finish(S, pts, hash_fn, plan=plan)


def threshold_aggregate_and_verify_sharded(
        batches, pks, msgs, mesh, rs=None, hash_fn=None):
    """Fused aggregate+verify, data-parallel over mesh axis "data" — the
    blocking composition of the three stages above (the shape the
    MULTICHIP dryrun and tests drive directly). Same contract as
    plane_agg.threshold_aggregate_and_verify: returns (compressed
    aggregates, all_valid), degrading to all_valid=False on an invalid or
    out-of-subgroup pubkey like the single-chip path. Completion routes
    through guard.finish_slot, so a device-class failure rides the
    fallback ladder here too."""
    from . import guard

    state = sharded_dispatch(batches, pks, msgs, mesh, rs=rs)
    return guard.finish_slot(state, (batches, pks, msgs), hash_fn)


@functools.lru_cache(maxsize=8)
def _build_verify_step(mesh, Bd: int):
    """The sharded multi-pairing check jit, cached per (mesh, per-device
    bucket): each device Miller-loops its Bd pair lanes and tree-folds
    them into one local Fq12 partial; the partials are all_gather'd (tiny
    — 12 Fq elements per device) and folded in-graph, and the single
    final exponentiation runs on the replicated product. Same verdict as
    pairing._compiled_pairing_check on one chip. On a multi-host GLOBAL
    mesh the all_gather spans hosts, so the cross-host Fq12 fold stays
    in-graph."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from . import pairing as pairing_mod
    from . import tower as TW

    D = mesh.devices.size

    def _local_check(p_x, p_y, q_x, q_y, mask):
        f = pairing_mod.miller_loop_pairs([(p_x, p_y)], [(q_x, q_y)])
        f = pairing_mod._select_fq12(mask, f, TW.fq12_one_like(q_x))
        f = pairing_mod._fq12_fold_product(f, Bd)
        g = jax.lax.all_gather(f, "data")
        parts = [(tuple(c[d] for c in g[0]), tuple(c[d] for c in g[1]))
                 for d in range(D)]
        while len(parts) > 1:
            nxt = [TW.fq12_mul(parts[k], parts[k + 1])
                   for k in range(0, len(parts) - 1, 2)]
            if len(parts) % 2:
                nxt.append(parts[-1])
            parts = nxt
        return pairing_mod.final_exp_is_one(parts[0])

    return jax.jit(shard_map(
        _local_check, mesh=mesh,
        in_specs=(P("data"),) * 5,
        out_specs=P(),
        check_vma=False,
    ))


@functools.lru_cache(maxsize=8)
def _build_miller_fold_step(mesh, Bd: int):
    """Chunked-verify analogue of _build_verify_step: per-device Miller
    loops + local fold, all_gather, in-graph cross-device fold — but NO
    final exponentiation. Returns the chunk's replicated Fq12 product so
    a >TILE-per-device pair set folds across chunks before the single
    final exp (pairing.fold_chunks_is_one). Also the per-host kernel of
    the bridged cluster verify (_sharded_check_multihost), where the
    cross-HOST products fold through the same finish graph."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from . import pairing as pairing_mod
    from . import tower as TW

    D = mesh.devices.size

    def _local_fold(p_x, p_y, q_x, q_y, mask):
        f = pairing_mod.miller_loop_pairs([(p_x, p_y)], [(q_x, q_y)])
        f = pairing_mod._select_fq12(mask, f, TW.fq12_one_like(q_x))
        f = pairing_mod._fq12_fold_product(f, Bd)
        g = jax.lax.all_gather(f, "data")
        parts = [(tuple(c[d] for c in g[0]), tuple(c[d] for c in g[1]))
                 for d in range(D)]
        while len(parts) > 1:
            nxt = [TW.fq12_mul(parts[k], parts[k + 1])
                   for k in range(0, len(parts) - 1, 2)]
            if len(parts) % 2:
                nxt.append(parts[-1])
            parts = nxt
        return parts[0]

    return jax.jit(shard_map(
        _local_fold, mesh=mesh,
        in_specs=(P("data"),) * 5,
        out_specs=P(),
        check_vma=False,
    ))


def _verify_placer(mesh, plan):
    """Input placement for the verify kernels: plain jnp.asarray except on
    a multi-host GLOBAL mesh, where each host contributes its own
    contiguous pair rows and make_array_from_process_local_data assembles
    the global operand (every host holds the full pair set, so slicing is
    free and placement-correct)."""
    if plan is None or plan.hosts <= 1 or plan.mode != "global":
        return jnp.asarray
    from jax.sharding import NamedSharding, PartitionSpec as P

    shard = NamedSharding(mesh, P("data"))
    W = mesh.devices.size
    D = W // plan.hosts
    lo_dev = plan.host_index * D

    def place(a):
        a = np.asarray(a)
        rows = a.shape[0] // W
        lo = lo_dev * rows
        return jax.make_array_from_process_local_data(
            shard, a[lo:lo + D * rows], a.shape)
    return place


def _sharded_check_chunked(p_x, p_y, q_x, q_y, mesh, plan=None) -> bool:
    """Pair sets too wide for one sharded dispatch (per-device bucket
    would exceed MAX_PAIR_TILE): successive D·TILE-pair sharded chunk
    dispatches, folded cross-chunk through the single-final-exp finish
    graph — the mesh analogue of pairing._pairing_check_chunked."""
    from . import pairing as pairing_mod

    n = p_x.shape[0]
    D = mesh.devices.size
    span = D * pairing_mod.MAX_PAIR_TILE
    arrs = tuple(np.asarray(a) for a in (p_x, p_y, q_x, q_y))
    place = _verify_placer(mesh, plan)
    parts = []
    for s in range(0, n, span):
        chunk = tuple(a[s:s + span] for a in arrs)
        m = chunk[0].shape[0]
        Bd = pairing_mod._bucket_pairs(-(-m // D))
        total = D * Bd

        def pad(a, total=total, m=m):
            if total == m:
                return a
            return np.concatenate([a, np.repeat(a[:1], total - m, axis=0)])

        mask = np.zeros(total, dtype=bool)
        mask[:m] = True
        parts.append(_build_miller_fold_step(mesh, Bd)(
            *(place(pad(a)) for a in chunk), place(mask)))
    return pairing_mod.fold_chunks_is_one(parts)


def _sharded_check_multihost(p_x, p_y, q_x, q_y, mesh, plan) -> bool:
    """Bridged-mode CLUSTER verify: the pair axis is chunked contiguously
    across hosts; each host Miller-loops and locally folds ONLY its range
    over its local mesh (re-chunked past MAX_PAIR_TILE exactly like
    _sharded_check_chunked), the per-chunk Fq12 products cross the
    HostLink under the slot's sequence tag, and EVERY host folds the
    full host-ordered product set in-graph through the single-final-exp
    finish (pairing.fold_chunks_is_one). The cross-host Fq12 fold stays
    in-graph — only ~12 Fq elements per chunk ride the wire — and all
    hosts agree on the verdict by construction (pairing
    multiplicativity: Π over hosts of Π over local pairs)."""
    from . import mesh as mesh_mod
    from . import pairing as pairing_mod

    n = p_x.shape[0]
    per = -(-n // plan.hosts)
    lo = min(n, plan.host_index * per)
    hi = min(n, (plan.host_index + 1) * per)
    arrs = tuple(np.asarray(a) for a in (p_x, p_y, q_x, q_y))
    D = mesh.devices.size
    span = D * pairing_mod.MAX_PAIR_TILE
    parts = []
    for s in range(lo, hi, span):
        chunk = tuple(a[s:min(s + span, hi)] for a in arrs)
        m = chunk[0].shape[0]
        Bd = pairing_mod._bucket_pairs(-(-m // D))
        total = D * Bd

        def pad(a, total=total, m=m):
            if total == m:
                return jnp.asarray(a)
            return jnp.asarray(
                np.concatenate([a, np.repeat(a[:1], total - m, axis=0)]))

        mask = np.zeros(total, dtype=bool)
        mask[:m] = True
        parts.append(_build_miller_fold_step(mesh, Bd)(
            *(pad(a) for a in chunk), jnp.asarray(mask)))
    payload = {"n": np.asarray([len(parts)], np.int64)}
    for i, f in enumerate(parts):
        for j, c in enumerate((*f[0], *f[1])):
            payload[f"p{i}c{j}"] = np.asarray(c)
    blobs = plan.link.exchange(f"slot/{plan.seq}/verify",
                               mesh_mod.pack_arrays(**payload))
    all_parts = []
    for hb, blob in enumerate(blobs):
        if hb == plan.host_index:
            all_parts.extend(parts)
            continue
        d = mesh_mod.unpack_arrays(blob)
        for i in range(int(d["n"][0])):
            cs = [jnp.asarray(d[f"p{i}c{j}"]) for j in range(6)]
            all_parts.append(((cs[0], cs[1], cs[2]),
                              (cs[3], cs[4], cs[5])))
    return pairing_mod.fold_chunks_is_one(all_parts)


def sharded_pairing_check(p_x, p_y, q_x, q_y, mesh, plan=None) -> bool:
    """Π e(Pᵢ, Qᵢ) == 1 with the pair axis sharded over mesh axis "data"
    — the mesh-wide analogue of pairing.pairing_check_planes (same plane
    layout, same masked lane-0 padding, same verdict). Pads the pair axis
    to D · Bd so every device gets an equal power-of-two bucket; for a
    typical slot (a handful of messages) each device Miller-loops two
    lanes and the collective moves one Fq12 per chip. When the per-device
    bucket would exceed MAX_PAIR_TILE the check runs chunked
    (_sharded_check_chunked) with a bit-identical verdict. A multi-host
    `plan` routes bridged topologies through the cluster verify
    (_sharded_check_multihost); on a global mesh the in-graph all_gather
    already spans hosts and only input placement changes."""
    from . import pairing as pairing_mod

    n = p_x.shape[0]
    if n == 0:
        return True
    if plan is not None and plan.hosts > 1 and plan.mode != "global" \
            and plan.link is not None:
        return _sharded_check_multihost(p_x, p_y, q_x, q_y, mesh, plan)
    D = mesh.devices.size
    Bd = pairing_mod._bucket_pairs(-(-n // D))
    if Bd > pairing_mod.MAX_PAIR_TILE:
        return _sharded_check_chunked(p_x, p_y, q_x, q_y, mesh, plan)
    total = D * Bd

    def pad(a):
        a = np.asarray(a)
        if total == n:
            return a
        return np.concatenate([a, np.repeat(a[:1], total - n, axis=0)])

    mask = np.zeros(total, dtype=bool)
    mask[:n] = True
    place = _verify_placer(mesh, plan)
    kernel = _build_verify_step(mesh, Bd)
    ok = kernel(place(pad(p_x)), place(pad(p_y)), place(pad(q_x)),
                place(pad(q_y)), place(mask))
    return pairing_mod._wait_verdict(ok, "_local_check")
