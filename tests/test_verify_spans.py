"""The bulk-verify path's tracer spans, and the tracer's profiler annotation.

Every `tracer.start_span` is also a `jax.profiler.TraceAnnotation` of its
name once JAX is loaded (and never imports JAX itself), so the program's
spans land in a profile beside the device programs. The bulk-verify path
(`TPUImpl.verify_batch` → `plane_agg.rlc_verify_batch`) opens one span per
layer, with an `ops/device_wait` span wherever the host blocks on the chip;
it runs inside `sentinel.region("verify")`, whose compiles also land as
`jax_compile` events on the open span. The path runs here on the CPU with
the device path forced and every device program stubbed, as
tests/test_device_verify.py and tests/test_plane_store.py do.
"""

from __future__ import annotations

import glob
import os
import subprocess
import sys
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from charon_tpu import tbls
from charon_tpu.crypto import curve as PC
from charon_tpu.crypto.curve import Fq2Ops, to_affine
from charon_tpu.ops import field as DF
from charon_tpu.ops import guard, h2c, pairing, plane_store, sentinel
from charon_tpu.ops import mesh as mesh_mod
from charon_tpu.ops import pallas_plane as PP
from charon_tpu.ops import plane_agg as PA
from charon_tpu.tbls import tpu_impl
from charon_tpu.tbls.types import PublicKey, Signature
from charon_tpu.utils import tracer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# the tracer's profiler annotation
# ---------------------------------------------------------------------------


def test_start_span_lands_in_a_profile_with_its_attributes(tmp_path):
    tracer.reset_for_testing()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracer.start_span("ops/test_span", n=3, path="device"):
            jnp.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    found = [dict(e.stats) for plane in
             jax.profiler.ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events
             if e.name == "ops/test_span"]
    assert found == [{"n": 3, "path": "device"}]
    assert [s.name for s in tracer.finished_spans()] == ["ops/test_span"]


def test_start_span_enters_a_trace_annotation_only_once_jax_is_loaded(
        monkeypatch):
    entered = []

    class Recorder:
        def __init__(self, name, **attrs):
            entered.append((name, attrs))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    with tracer.start_span("ops/a", lanes=8):
        pass
    monkeypatch.delitem(sys.modules, "jax")
    with tracer.start_span("ops/b"):
        pass
    assert entered == [("ops/a", {"lanes": 8})]


def test_the_tracer_does_not_import_jax():
    code = ("import sys\n"
            "from charon_tpu.utils import tracer\n"
            "with tracer.start_span('core/duty', slot=1):\n"
            "    pass\n"
            "assert tracer.finished_spans()[0].name == 'core/duty'\n"
            "print('jax' in sys.modules)\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False"


def test_set_attrs_sets_the_open_span_only():
    tracer.set_attrs(ignored=1)  # no span open: nothing happens
    with tracer.start_span("ops/outer") as outer:
        with tracer.start_span("ops/inner") as inner:
            tracer.set_attrs(misses=2)
    assert inner.attrs == {"misses": 2} and outer.attrs == {}


# ---------------------------------------------------------------------------
# the bulk-verify path's spans, with the device programs stubbed
# ---------------------------------------------------------------------------


N = 4
MSG = b"\x42" * 32


@pytest.fixture
def stubbed_device(monkeypatch):
    """TPUImpl.verify_batch on its device path, every program stubbed: the
    host code between them (parse, digit planes, folds, pairing inputs,
    caches) runs for real. Records the sentinel region each program saw."""
    guard.reset_for_testing()
    tracer.reset_for_testing()
    monkeypatch.setenv("CHARON_TPU_DEVICE_VERIFY", "1")
    monkeypatch.setattr(tpu_impl, "_on_device", lambda: True)
    monkeypatch.setattr(PA, "_device_path", lambda n=1 << 30: True)
    monkeypatch.setattr(plane_store, "STORE", plane_store.PlaneStore())
    monkeypatch.setattr(PA, "_h2c_cache", OrderedDict())
    monkeypatch.setattr(mesh_mod, "sigagg_mesh", lambda: None)
    regions = []

    def decompress(Xr, splane, lmask):
        regions.append(sentinel._current_region())
        return Xr, Xr, Xr, np.ones(lmask.shape, bool)

    def verify_slot(X0r, X1r, sgn, lmask, rdig, pkX, pkY, pkZ, gmask, *, G):
        regions.append(sentinel._current_region())
        return np.ones(lmask.shape, bool), np.bool_(True), ("sig",), \
            [("pk",)] * G

    folds = {"sig": PC.g2_generator(),
             "pk": tuple((c, 0) for c in PC.g1_generator())}
    gx, gy = to_affine(Fq2Ops, PC.g2_generator())

    def map_to_g2(u0, u1, s0, s1):
        B = u0.shape[0]
        return (np.broadcast_to(DF.fq2_from_ints(*gx), (B, 2, DF.LIMBS)),
                np.broadcast_to(DF.fq2_from_ints(*gy), (B, 2, DF.LIMBS)))

    monkeypatch.setattr(PA, "_g1_decompress_jit", decompress)
    monkeypatch.setattr(PA, "_g1_subgroup_jit",
                        lambda X, Y, Z: np.bool_(True))
    monkeypatch.setattr(PA, "_verify_slot_jit", verify_slot)
    monkeypatch.setattr(PP, "_host_fold", lambda tag, E: folds[tag])
    monkeypatch.setattr(h2c, "map_to_g2_device", map_to_g2)
    monkeypatch.setattr(pairing, "_compiled_pairing_check",
                        lambda Bp: (lambda *a: np.array([True])))
    impl = tpu_impl.TPUImpl()
    impl.min_device_verify = N
    impl.fallback_on_device_error = False
    yield impl, regions
    guard.reset_for_testing()


def _set():
    sks = [tbls.generate_secret_key() for _ in range(N)]
    return ([PublicKey(bytes(tbls.secret_to_public_key(sk))) for sk in sks],
            [MSG] * N,
            [Signature(bytes(tbls.sign(sk, MSG))) for sk in sks])


def _tree(spans, parent=None):
    """[(name, attrs, children)] of the spans under `parent`, in order."""
    return [(s.name, s.attrs, _tree(spans, s.span_id))
            for s in sorted(spans, key=lambda s: s.start)
            if s.parent_id == parent]


def _wait(program):
    return ("ops/device_wait", {"program": program}, [])


def test_bulk_verify_spans_nest_as_the_call_tree(stubbed_device):
    impl, regions = stubbed_device
    pks, msgs, sigs = _set()
    assert impl.verify_batch(pks, msgs, sigs) is True
    assert _tree(tracer.finished_spans()) == [
        ("tbls/verify_batch", {"n": N, "path": "device"}, [
            ("ops/verify/pk_planes", {"chunks": 1, "misses": 1}, [
                ("ops/planestore/decode_chunks", {"chunks": 1}, [
                    _wait("_g1_decompress_jit"),
                    _wait("_g1_subgroup_jit")])]),
            ("ops/verify/pack", {"lanes": N, "groups": 1}, []),
            ("ops/verify/fold", {"chunks": 1}, [_wait("_verify_slot_jit")]),
            ("ops/verify/pairing", {"pairs": 2, "path": "device"}, [
                ("ops/verify/h2c", {"hits": 0, "misses": 1},
                 [_wait("map_to_g2")]),
                _wait("pairing_check")])])]
    assert regions == ["verify", "verify"]


def test_a_repeated_set_hits_the_plane_store_and_the_h2c_cache(
        stubbed_device):
    impl, _regions = stubbed_device
    pks, msgs, sigs = _set()
    impl.verify_batch(pks, msgs, sigs)
    tracer.reset_for_testing()
    assert impl.verify_batch(pks, msgs, sigs) is True
    (root,) = _tree(tracer.finished_spans())
    assert [(n, a) for n, a, _c in root[2]] == [
        ("ops/verify/pk_planes", {"chunks": 1, "misses": 0}),
        ("ops/verify/pack", {"lanes": N, "groups": 1}),
        ("ops/verify/fold", {"chunks": 1}),
        ("ops/verify/pairing", {"pairs": 2, "path": "device"})]
    pairing_children = root[2][3][2]
    assert pairing_children == [
        ("ops/verify/h2c", {"hits": 1, "misses": 0}, []),
        _wait("pairing_check")]


def test_a_small_set_takes_the_native_path_under_its_span(stubbed_device):
    impl, _regions = stubbed_device
    pks, msgs, sigs = _set()
    assert impl.verify_batch(pks[:2], msgs[:2], sigs[:2]) is True
    assert [(s.name, s.attrs) for s in tracer.finished_spans()] == [
        ("tbls/verify_batch", {"n": 2, "path": "native"})]


# ---------------------------------------------------------------------------
# which step recompiled
# ---------------------------------------------------------------------------


def _fresh_compile(k: float) -> None:
    jax.jit(lambda x: x * k + 1.0)(np.ones(3, np.float32)).block_until_ready()


def test_a_compile_inside_the_verify_path_counts_under_verify(
        stubbed_device, monkeypatch):
    impl, _regions = stubbed_device
    sentinel.install()

    def subgroup(X, Y, Z):
        _fresh_compile(1.25)
        return np.bool_(True)

    monkeypatch.setattr(PA, "_g1_subgroup_jit", subgroup)
    before = sentinel._compiles_c.value("verify")
    pks, msgs, sigs = _set()
    impl.verify_batch(pks, msgs, sigs)
    assert sentinel._compiles_c.value("verify") == before + 1


def test_a_compile_adds_a_jax_compile_event_to_the_open_span():
    sentinel.install()
    with tracer.start_span("core/duty") as span:
        with sentinel.region("slot"):
            _fresh_compile(2.5)
    (ev,) = [e for e in span.events if e.name == "jax_compile"]
    assert ev.attrs["region"] == "slot"
    assert ev.attrs["seconds"] > 0
    assert "lambda" in ev.attrs["fun"]


# ---------------------------------------------------------------------------
# ops_device_dispatch_seconds past the slot
# ---------------------------------------------------------------------------


def test_dispatch_histogram_buckets_run_past_the_slot():
    hist = PA._dispatch_hist
    assert hist.buckets[-3:] == (10, 15, 30)
    before = hist._counts.get(("verify",), [0] * (len(hist.buckets) + 1))[:]
    hist.observe(7.5, "verify")
    after = hist._counts[("verify",)]
    grew = [i for i, (a, b) in enumerate(zip(before, after)) if a != b]
    assert [hist.buckets[i] for i in grew] == [10]
    assert "wait" in hist.help
