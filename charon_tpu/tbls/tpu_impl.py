"""TPU backend for the tbls facade — the north-star offload.

Routes the duty pipeline's hot calls onto the fused Pallas kernel plane
(ops/pallas_plane.py, ops/plane_agg.py):

  * threshold_aggregate_batch — per-validator Lagrange combination Σ λⱼ·sigⱼ
    for a whole batch of validators in one device double-and-add sweep
    (reference hot loop: core/sigagg/sigagg.go:144). Bit-identical to the
    CPU backends: all three compute Σ λⱼ·sigⱼ exactly with the same ETH
    serialization (the cross-implementation randomized suite, reference
    tbls/tbls_test.go:210-240, holds across the triple).
  * verify_batch — random-linear-combination batch verification: device
    G1/G2 MSMs with 64-bit coefficients, then the folded multi-pairing
    check itself on device — hash-to-curve (ops/h2c.py), per-pair Miller
    loops and one final exponentiation in a single batched dispatch
    (plane_agg._pairing_finish), with the native ctypes ct_pairing_check
    kept as the guard's fallback rung (reference hot loops: per-partial
    tbls.Verify in core/parsigex/parsigex.go:61 and the aggregate verify
    in core/sigagg/sigagg.go:159). Sound to 2⁻⁶⁴ per batch (eth2-client
    batch-verification practice, blst mult-verify); a False means at least
    one bad signature and callers attribute per-item. Path split is
    observable as ops_pairing_total{path}.

Everything else (keygen, split/recover, sign, single verify) delegates to
the native C++ backend — key material never rides this backend's device
path. (The DKG's batched keygen is a separate, explicitly opt-in
trusted-device path: dkg/frost.enable_device_keygen.) Small batches stay on the CPU: a fused device
call has a fixed floor (~0.36 s aggregate+verify, ~0.20 s bulk verify —
one dispatch + one transfer, round-3 single-dispatch design) regardless
of batch size ≤1024, so it only wins past `min_device_batch` /
`min_device_verify` items; the cross-duty batching window
(core/coalesce.py) gathers sub-threshold duties up to these sizes.

Multi-device hosts: every fused sigagg entry point here
(threshold_aggregate_verify_batch / _overlapped / _submit) dispatches
through plane_agg._dispatch_slot, which consults the ops.mesh seam — on a
>1-device mesh the slot's validator axis is sharded P("data") across all
local devices (ops/sharded_plane.py) with identical outputs and
bad_pk/FIFO semantics; with one device (or CHARON_TPU_SIGAGG_DEVICES=1)
the exact single-device path runs, bit-identical to prior builds.
Feature-gated in app wiring via
charon_tpu.utils.featureset.TPU_BLS, mirroring how the reference gates
backends behind tbls.SetImplementation + app/featureset
(reference tbls/tbls.go:72, featureset.go:10-75).
"""

from __future__ import annotations

import concurrent.futures as futures
import threading

import numpy as np

from ..utils import tracer
from .native_impl import NativeImpl
from .types import PublicKey, Signature


def _device_runtime_errors() -> tuple:
    """Exception types meaning the DEVICE failed at runtime — distinct
    from input-validation ValueErrors, which must propagate. A transient
    device fault must not fail a duty: the batch falls back to the native
    CPU path (same results, slower), like the
    reference's tolerance of individual BN failures. ops.guard ladders
    most of these away before they reach this layer; this tuple is the
    last-resort belt over the guard's braces (and TimeoutError covers an
    exhausted watchdog ladder). faults.DeviceLostFault is the chaos
    seam's injected stand-in, so chaos runs degrade identically to real
    losses even where jax raises a different concrete type."""
    from ..utils import faults

    base: tuple = (faults.DeviceLostFault, TimeoutError)
    try:
        import jax

        return base + (jax.errors.JaxRuntimeError,)
    except Exception:  # noqa: BLE001 — no jax, no device errors
        return base


_DEVICE_RUNTIME_ERRORS = _device_runtime_errors()


def _warn_device_fallback(op: str, exc: Exception) -> None:
    from ..utils import log

    log.with_topic("tbls").warn("device dispatch failed; native fallback",
                                op=op, err=str(exc)[:200])


def _on_device() -> bool:
    import jax

    return jax.default_backend() != "cpu"


def cpu_requested() -> bool:
    """Whether JAX was pinned to the CPU on purpose (JAX_PLATFORMS=cpu or
    the jax_platforms option) — the one case where TPUImpl may run on a
    host without an accelerator, keeping every call on the native path."""
    import jax

    return (jax.config.jax_platforms or "").strip().lower() == "cpu"


_PIPELINE = None
_PIPELINE_LOCK = threading.Lock()


def _shared_pipeline():
    """Process-wide SigAggPipeline: one device, one dispatch queue — every
    TPUImpl instance overlaps through the same three-stage pipeline (depth
    and stage-3 executor width resolved through the SlotPolicy seam:
    installed policy → CHARON_TPU_PIPELINE_DEPTH / CHARON_TPU_FINISH_WORKERS
    env → defaults). The pipeline subscribes to policy installs so a tuner
    move to either knob is adopted between slots without a rebuild."""
    global _PIPELINE
    with _PIPELINE_LOCK:
        if _PIPELINE is None:
            from ..ops import plane_agg
            from ..ops import policy as policy_mod

            _PIPELINE = plane_agg.SigAggPipeline()
            policy_mod.subscribe(_PIPELINE.apply_policy)
        return _PIPELINE


class TPUImpl(NativeImpl):
    """tbls Implementation running batched ops on the JAX device."""

    name = "jax-tpu"

    # Below this many items the fixed device-call floor loses to the native
    # per-item path; tuned on v5e with the round-3 single-dispatch path
    # (bench_scale.py: fused aggregate+verify floor ~0.36s vs native
    # ~9.3ms/validator -> breakeven ~40; bulk verify floor ~0.20s vs native
    # ~1.9ms/sig -> breakeven ~107; both with a safety margin). The
    # coalescer (core/coalesce.py) batches sub-threshold duties up to
    # these sizes.
    min_device_batch = 64     # threshold_aggregate paths
    min_device_verify = 128   # verify_batch
    # chip_smoke.py and the benches set False so a device fault raises
    # instead of silently running (and timing) the native path
    fallback_on_device_error = True

    def threshold_aggregate_batch(self, batches: list[dict[int, Signature]]
                                  ) -> list[Signature]:
        if len(batches) < self.min_device_batch or not _on_device():
            return NativeImpl.threshold_aggregate_batch(self, batches)
        for b in batches:
            if not b:
                raise ValueError("no partial signatures to aggregate")
        from ..ops import plane_agg

        try:
            raw = plane_agg.threshold_aggregate_batch(
                [{i: bytes(s) for i, s in b.items()} for b in batches])
        except _DEVICE_RUNTIME_ERRORS as exc:
            if not self.fallback_on_device_error:
                raise
            _warn_device_fallback("threshold_aggregate_batch", exc)
            return NativeImpl.threshold_aggregate_batch(self, batches)
        return [Signature(r) for r in raw]

    def verify_batch(self, public_keys: list[PublicKey], datas: list[bytes],
                     signatures: list[Signature]) -> bool:
        if not (len(public_keys) == len(datas) == len(signatures)):
            raise ValueError("length mismatch")
        n = len(public_keys)
        device = n >= self.min_device_verify and _on_device()
        # the whole call, host work and device waits alike: the span the
        # bulk-verify path's ops/verify/* and ops/device_wait spans nest in
        with tracer.start_span("tbls/verify_batch", n=n,
                               path="device" if device else "native") as span:
            if not device:
                return NativeImpl.verify_batch(self, public_keys, datas,
                                               signatures)
            # Curve membership + infinity rejection run in
            # rlc_verify_batch's bulk native decode; subgroup membership
            # runs batched on device (endomorphism checks), matching the
            # native per-item verifier's semantics.
            from ..ops import plane_agg

            try:
                return plane_agg.rlc_verify_batch(
                    [bytes(pk) for pk in public_keys],
                    [bytes(d) for d in datas],
                    [bytes(s) for s in signatures])
            except _DEVICE_RUNTIME_ERRORS as exc:
                if not self.fallback_on_device_error:
                    raise
                _warn_device_fallback("verify_batch", exc)
                span.attrs["path"] = "native"
                return NativeImpl.verify_batch(self, public_keys, datas,
                                               signatures)

    def threshold_aggregate_verify_batch(self, batches, public_keys, datas):
        """Fused device pass: the RLC verification consumes the freshly
        computed aggregate plane (no serialize→decompress round trip and no
        redundant subgroup check — aggregates of in-subgroup partials stay
        in the subgroup)."""
        n = len(batches)
        if not (n == len(public_keys) == len(datas)):
            raise ValueError("length mismatch")
        if n < self.min_device_batch or not _on_device():
            return NativeImpl.threshold_aggregate_verify_batch(
                self, batches, public_keys, datas)
        for b in batches:
            if not b:
                raise ValueError("no partial signatures to aggregate")
        from ..ops import plane_agg

        try:
            raw, ok = plane_agg.threshold_aggregate_and_verify(
                [{i: bytes(s) for i, s in b.items()} for b in batches],
                [bytes(pk) for pk in public_keys], [bytes(d) for d in datas])
        except _DEVICE_RUNTIME_ERRORS as exc:
            if not self.fallback_on_device_error:
                raise
            _warn_device_fallback("threshold_aggregate_verify_batch", exc)
            return NativeImpl.threshold_aggregate_verify_batch(
                self, batches, public_keys, datas)
        return [Signature(r) for r in raw], ok

    def threshold_aggregate_verify_overlapped(self, batches, public_keys,
                                              datas):
        """Double-buffered fused sigagg: identical inputs/outputs to
        threshold_aggregate_verify_batch, but the slot dispatches through
        the process-wide SigAggPipeline, whose lock covers only the host
        pack+dispatch — a CONCURRENT call (the coalescer's executor
        threads on back-to-back flushes) packs its buffers while this
        slot's fused graph executes on device, instead of serializing
        pack→dispatch→wait end to end. Rides submit_async so the slot's
        finish runs as the pipeline's chained emit→verify stage-3 tasks:
        this slot's verify dispatch overlaps the next caller's pack
        instead of blocking it out on the calling thread."""
        n = len(batches)
        if not (n == len(public_keys) == len(datas)):
            raise ValueError("length mismatch")
        if n < self.min_device_batch or not _on_device():
            # degrade to the serial entry point, which owns the
            # device-vs-native decision (and is the seam callers spy on)
            return self.threshold_aggregate_verify_batch(
                batches, public_keys, datas)
        for b in batches:
            if not b:
                raise ValueError("no partial signatures to aggregate")
        try:
            raw, ok = _shared_pipeline().submit_async(
                [{i: bytes(s) for i, s in b.items()} for b in batches],
                [bytes(pk) for pk in public_keys],
                [bytes(d) for d in datas]).result()
        except _DEVICE_RUNTIME_ERRORS as exc:
            if not self.fallback_on_device_error:
                raise
            _warn_device_fallback("threshold_aggregate_verify_overlapped",
                                  exc)
            return NativeImpl.threshold_aggregate_verify_batch(
                self, batches, public_keys, datas)
        return [Signature(r) for r in raw], ok

    def _resolved(self, call) -> futures.Future:
        """Run `call` inline and wrap its outcome in a resolved Future —
        the no-pipeline shape of the submit path."""
        fut: futures.Future = futures.Future()
        try:
            fut.set_result(call())
        except Exception as exc:  # noqa: BLE001 — future carries the error
            fut.set_exception(exc)
        return fut

    def threshold_aggregate_verify_submit(self, batches, public_keys,
                                          datas) -> futures.Future:
        """Future-returning fused sigagg: pack + dispatch on the CALLING
        thread (so input-validation surfaces eagerly through the future
        and ordering follows call order), then resolve from the pipeline's
        stage-3 finish worker. Sub-threshold/deviceless batches run the
        serial entry point inline and return an already-resolved future.
        The device-fault fallback policy matches the blocking entry
        points: a _DEVICE_RUNTIME_ERRORS failure (at dispatch OR surfacing
        through the finish) degrades to the native CPU path instead of
        failing the duty."""
        n = len(batches)
        if not (n == len(public_keys) == len(datas)):
            raise ValueError("length mismatch")
        if n < self.min_device_batch or not _on_device():
            return self._resolved(
                lambda: self.threshold_aggregate_verify_batch(
                    batches, public_keys, datas))
        for b in batches:
            if not b:
                raise ValueError("no partial signatures to aggregate")
        try:
            inner = _shared_pipeline().submit_async(
                [{i: bytes(s) for i, s in b.items()} for b in batches],
                [bytes(pk) for pk in public_keys], [bytes(d) for d in datas])
        except _DEVICE_RUNTIME_ERRORS as exc:
            if not self.fallback_on_device_error:
                raise
            _warn_device_fallback("threshold_aggregate_verify_submit", exc)
            return self._resolved(
                lambda: NativeImpl.threshold_aggregate_verify_batch(
                    self, batches, public_keys, datas))

        out: futures.Future = futures.Future()

        def _done(f: futures.Future) -> None:
            try:
                raw, ok = f.result()
            except _DEVICE_RUNTIME_ERRORS as exc:
                if not self.fallback_on_device_error:
                    out.set_exception(exc)
                    return
                _warn_device_fallback("threshold_aggregate_verify_submit",
                                      exc)
                try:
                    out.set_result(NativeImpl.threshold_aggregate_verify_batch(
                        self, batches, public_keys, datas))
                except Exception as exc2:  # noqa: BLE001 — carried by future
                    out.set_exception(exc2)
            except Exception as exc:  # noqa: BLE001 — carried by future
                out.set_exception(exc)
            else:
                out.set_result(([Signature(r) for r in raw], ok))

        inner.add_done_callback(_done)
        return out

    def pin_pubkeys(self, public_keys) -> None:
        """Pin the set's decoded planes in the device PlaneStore so cache
        pressure from transient sets can never evict the cluster's own
        share/root pubkeys (core/sigagg pins at construction). Pinning is
        by full-set digest, so the sharded per-device pk placements
        (PlaneStore.sharded_entry) are protected by the same pin."""
        from ..ops import plane_store

        plane_store.STORE.pin([bytes(pk) for pk in public_keys])

    def verify_batch_each(self, public_keys: list[PublicKey],
                          datas: list[bytes],
                          signatures: list[Signature]) -> np.ndarray:
        """Per-item validity — the attribution path after a failed batch.
        Native per-item verification: exact culprits, no RLC ambiguity."""
        if not (len(public_keys) == len(datas) == len(signatures)):
            raise ValueError("length mismatch")
        return np.asarray([
            self.verify(pk, data, sig)
            for pk, data, sig in zip(public_keys, datas, signatures)
        ], dtype=bool)
