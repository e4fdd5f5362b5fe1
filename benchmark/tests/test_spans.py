"""The program's spans read from a traced run: the reader, the two clock
anchors, the idle gaps' labels and the two per-layer metrics built on them.

`data/spans_trace.xplane.pb` is a recorded CPU profile (made by
`data/record_spans_trace.py`) of three closed-loop calls, each a
`tbls/verify_batch` span holding `ops/verify/pk_planes`, `ops/verify/pack`
and `ops/device_wait` spans around two tiny jitted functions. On the CPU the
host and the programs share one clock, so each anchor's shift brackets 0.
`data/cpu_trace.xplane.pb` holds no program span, as a build of the program
without them would not.
"""

import os
import shutil
from types import SimpleNamespace

import pytest

import run
import spans
import trace_reduce
from conftest import CELL

DATA = os.path.join(os.path.dirname(__file__), "data")
SPANS = os.path.join(DATA, "spans_trace.xplane.pb")
PLAIN = os.path.join(DATA, "cpu_trace.xplane.pb")
US, MS, S = 1_000, 1_000_000, 1_000_000_000


# ---------------------------------------------------------------------------
# the anchors, on synthetic traces
# ---------------------------------------------------------------------------


def _synthetic(off: int):
    """Two programs on a device clock `off` ahead of the host's: each is
    launched 20 us before it starts and waited on until 30 us after it
    ends."""
    modules = [("jit__verify_slot_jit(3)", off + 1 * MS, off + 2 * MS, 7),
               ("jit_pairing_check(9)", off + 5 * MS, off + 9 * MS, 8)]
    launches = {7: 1 * MS - 20 * US, 8: 5 * MS - 20 * US}
    found = [("tbls/verify_batch", 0, 10 * MS, {}),
             ("ops/verify/pack", 100 * US, 1 * MS, {}),
             ("ops/device_wait", 1 * MS, 2 * MS + 30 * US,
              {"program": "_verify_slot_jit"}),
             ("ops/verify/pairing", 2 * MS + 40 * US, 9 * MS + 50 * US, {}),
             ("ops/device_wait", 5 * MS, 9 * MS + 30 * US,
              {"program": "pairing_check"})]
    return modules, launches, found


def test_the_launch_anchor_is_exact_up_to_the_launch_latency():
    off = 5 * S
    modules, launches, found = _synthetic(off)
    s = spans.align(spans.Spans(found), modules, launches)
    assert s.anchor == "launch" and s.shift == off + 20 * US
    assert s.bounds == {"launch": off + 20 * US, "wait": off - 30 * US}


def test_the_wait_anchor_stands_in_where_no_run_id_links_them():
    off = -3 * S
    modules, _launches, found = _synthetic(off)
    s = spans.align(spans.Spans(found), modules, {})
    assert s.anchor == "wait" and s.shift == off - 30 * US


def test_a_launch_anchor_below_the_wait_anchor_is_not_used():
    modules, launches, found = _synthetic(0)
    late = {r: t + 1 * MS for r, t in launches.items()}  # not a launch
    s = spans.align(spans.Spans(found), modules, late)
    assert s.anchor == "wait" and s.shift == -30 * US


def test_waits_and_runs_that_do_not_pair_give_no_wait_anchor():
    modules, _launches, found = _synthetic(0)
    found.append(("ops/device_wait", 9 * MS, 9 * MS + 1, {"program":
                                                         "pairing_check"}))
    s = spans.align(spans.Spans(found), modules[1:], {})
    assert s.anchor is None and s.shift is None and s.label(0, 1) is None


def test_a_gap_takes_the_innermost_program_span_and_falls_back_to_bench():
    off = 5 * S
    modules, launches, found = _synthetic(off)
    s = spans.align(spans.Spans(found), modules, launches)
    ops = [(n, a, b) for n, a, b, _r in modules]
    tr = trace_reduce.Trace(
        ops={"/device:TPU:0": ops}, modules=ops,
        host=[("bench.window", off + 200 * US, off + 13 * MS),
              ("bench.submit", off + 200 * US, off + 13 * MS)])
    labels = dict((round(g * 1e6), n) for n, g in spans.labelled_gaps(tr, s))
    # device idle (device ms): [0.2, 1) in pack, [2, 5) in the pairing's
    # host part before its wait, [9, 13) after the call, in bench.submit
    assert labels == {800: "ops/verify/pack", 3000: "ops/verify/pairing",
                      4000: "bench.submit"}


def test_host_time_is_the_span_less_the_union_of_its_waits():
    modules, launches, found = _synthetic(0)
    s = spans.align(spans.Spans(found, window=(0, 10 * MS)), modules,
                    launches)
    waits = (1 * MS + 30 * US) + (4 * MS + 30 * US)
    assert s.less_waits_ms("tbls/verify_batch") == pytest.approx(
        (10 * MS - waits) / MS)
    assert s.ms("ops/verify/pack") == pytest.approx(0.9)
    s.window = (0, 5 * MS)  # the call is not inside it
    assert s.in_window("tbls/verify_batch") == []


# ---------------------------------------------------------------------------
# the recorded CPU trace
# ---------------------------------------------------------------------------


def test_recorded_trace_spans_nest_and_both_anchors_bracket_zero():
    s = spans.read(SPANS)
    names = [n for n, _a, _b, _s in sorted(s.spans, key=lambda x: x[1])]
    assert names == ["tbls/verify_batch", "ops/verify/pk_planes",
                     "ops/device_wait", "ops/verify/pack",
                     "ops/device_wait"] * 3
    assert [a["program"] for n, _t0, _t1, a in s.spans
            if n == "ops/device_wait"] == ["step", "other"] * 3
    assert s.bounds["wait"] <= 0 <= s.bounds["launch"]
    assert s.bounds["launch"] < 1 * MS and s.bounds["wait"] > -1 * MS
    assert s.anchor == "launch" and s.submit_shift is None  # one clock
    for name, t0, t1, _a in s.spans:  # the spans lie inside the window
        assert s.window[0] <= t0 <= t1 <= s.window[1], name


def test_recorded_trace_host_time_and_pubkey_plane_time():
    s = spans.read(SPANS)
    calls = s.in_window("tbls/verify_batch")
    waits = [(a, b) for n, a, b, _s in s.spans if n == "ops/device_wait"]
    want = sum((b - a) - sum(min(w1, b) - max(w0, a) for w0, w1 in waits
                             if w0 < b and w1 > a)
               for a, b, _x in calls) / MS
    assert len(calls) == 3
    assert s.less_waits_ms("tbls/verify_batch") == pytest.approx(want)
    assert s.less_waits_ms("tbls/verify_batch") > 3 * 2.0  # the 2 ms sleeps
    assert s.ms("ops/verify/pk_planes") == pytest.approx(sum(
        b - a for n, a, b, _x in s.spans if n == "ops/verify/pk_planes") / MS)


def test_recorded_trace_gaps_inside_a_call_carry_a_program_span():
    s = spans.read(SPANS)
    tr = trace_reduce.load(SPANS)
    calls = [(a + s.shift, b + s.shift)
             for a, b, _x in s.in_window("tbls/verify_batch")]
    gaps = spans.idle_gaps(tr)
    labels = spans.labelled_gaps(tr, s, top=len(gaps))
    assert sorted(g for _n, g in labels) == sorted(
        (b - a) / 1e9 for a, b in gaps)
    inside = [(a, b) for a, b in gaps if b - a >= 500 * US
              and any(c0 <= (a + b) // 2 <= c1 for c0, c1 in calls)]
    assert len(inside) >= 3  # each call's 2 ms of host work in pack
    named = {round((b - a) / 1e9, 12): s.label(a, b) for a, b in inside}
    assert set(named.values()) == {"ops/verify/pack"}


# ---------------------------------------------------------------------------
# the readers, on a run laid out as run.py leaves it
# ---------------------------------------------------------------------------


def _run(tmp_path, monkeypatch, xplane, n=3):
    tdir = tmp_path / ".trace" / CELL / "plugins" / "profile" / "t"
    tdir.mkdir(parents=True)
    shutil.copy(xplane, tdir / "host.xplane.pb")
    monkeypatch.setattr(spans, "HERE", str(tmp_path))
    spans._CACHE.clear()
    summary = trace_reduce.summarize(trace_reduce.load(xplane))
    return SimpleNamespace(
        cell=SimpleNamespace(workload={"name": CELL}), trace=summary,
        window=SimpleNamespace(completed=[object()] * n))


def test_the_two_readers_divide_by_the_traced_sets(tmp_path, monkeypatch):
    r = _run(tmp_path, monkeypatch, SPANS)
    s = spans.read(SPANS)
    host = run.reader("verify_host_ms.parsigex", traced=True)(r)
    pk = run.reader("pk_planes_ms.parsigex", traced=True)(r)
    assert host == pytest.approx(s.less_waits_ms("tbls/verify_batch") / 3)
    assert pk == pytest.approx(s.ms("ops/verify/pk_planes") / 3)
    r.trace = None  # an untraced run
    assert run.reader("pk_planes_ms.parsigex", traced=True)(r) is None


def test_a_trace_without_program_spans_reads_nothing(tmp_path, monkeypatch,
                                                     capsys):
    r = _run(tmp_path, monkeypatch, PLAIN)
    for name in ("verify_host_ms.parsigex", "pk_planes_ms.parsigex"):
        assert run.reader(name, traced=True)(r) is None
    assert "no program span" in capsys.readouterr().err


def test_the_new_metrics_are_listed_for_the_cell():
    cell = run.resolve(run.ROOT, CELL, traced=True)
    got = {m["name"]: m for m in cell.metrics}
    for name, layer in (("verify_host_ms.parsigex", "host"),
                        ("pk_planes_ms.parsigex", "pubkey planes")):
        assert got[name]["source"] == "program_span"
        assert got[name]["layer"] == layer
        assert got[name]["moves"] == "verify_sigs_per_s"


# ---------------------------------------------------------------------------
# the accepted readings of the recorded trace stay as they were
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("metric,want", [
    ("device_idle_share.parsigex", 0.831963113680389),
    ("verify_graph_device_ms", None),       # no _verify_slot_jit on the CPU
    ("h2c_pairing_device_ms.parsigex", None),
])
def test_the_accepted_trace_metrics_read_as_before(metric, want):
    summary = trace_reduce.summarize(trace_reduce.load(PLAIN))
    r = SimpleNamespace(trace=summary,
                        window=SimpleNamespace(completed=[object()]))
    got = run.reader(metric, traced=True)(r)
    assert got == (pytest.approx(want, rel=1e-12) if want else None)


def test_the_accepted_summary_of_the_recorded_trace_is_unchanged():
    s = trace_reduce.summarize(trace_reduce.load(PLAIN))
    assert (s.busy_s, s.window_s) == (0.001367462, 0.008137868)
    assert [n for n, _g in s.idle_gaps] == ["host"] * 3 + ["bench.submit"] * 7
