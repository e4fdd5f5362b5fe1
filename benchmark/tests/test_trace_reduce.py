"""The reduction from a profiler trace to busy time, idle share, device
seconds per program, top operations and labelled idle gaps.

`data/cpu_trace.xplane.pb` is a recorded CPU profile of two tiny jitted
functions (`step`, `other`), called three times inside a `bench.window`
annotation with `bench.submit` / `bench.wait` around the calls.
"""

import os

import pytest

import trace_reduce

DATA = os.path.join(os.path.dirname(__file__), "data", "cpu_trace.xplane.pb")


def _events():
    from jax.profiler import ProfileData

    ops, window = [], None
    for plane in ProfileData.from_file(DATA).planes:
        for line in plane.lines:
            for e in line.events:
                stats = dict(e.stats)
                if "hlo_op" in stats:
                    ops.append((stats["hlo_module"], stats["run_id"],
                                int(e.start_ns), int(e.end_ns)))
                elif e.name == "bench.window":
                    window = (int(e.start_ns), int(e.end_ns))
    return ops, window


def test_recorded_trace_busy_union_and_idle_share():
    ops, (lo, hi) = _events()
    covered = set()  # microsecond ticks covered by any operation
    for _m, _r, a, b in ops:
        covered.update(range(max(a, lo) // 1000, min(b, hi) // 1000))
    s = trace_reduce.summarize(trace_reduce.load(DATA))
    assert s.window_s == pytest.approx((hi - lo) / 1e9)
    assert s.busy_s == pytest.approx(len(covered) / 1e6, abs=2e-5)
    assert 0 < s.busy_s < s.window_s
    assert s.idle_share == pytest.approx(1 - s.busy_s / s.window_s)


def test_recorded_trace_device_seconds_per_program():
    ops, _ = _events()
    runs = {}
    for module, run, a, b in ops:
        lo, hi = runs.get((module, run), (a, b))
        runs[(module, run)] = (min(lo, a), max(hi, b))
    want = {m: sum(b - a for (mm, _r), (a, b) in runs.items() if mm == m) / 1e9
            for m in ("jit_step", "jit_other")}
    s = trace_reduce.summarize(trace_reduce.load(DATA))
    assert s.module_seconds("step") == pytest.approx(want["jit_step"])
    assert s.module_seconds("other") == pytest.approx(want["jit_other"])
    assert s.module_seconds("step", "other") == pytest.approx(
        want["jit_step"] + want["jit_other"])
    assert s.module_seconds("ste") == 0  # whole names only
    assert len(s.device_ops) <= 10 and len(s.idle_gaps) <= 10
    assert all(g[1] > 0 for g in s.idle_gaps)


def test_device_planes_busy_gaps_and_labels():
    """A TPU-shaped trace: two devices, ops on each, programs on device 0,
    and host annotations on the same clock."""
    ms = 1_000_000
    tr = trace_reduce.Trace(
        ops={"/device:TPU:0": [("a", 1 * ms, 3 * ms), ("b", 2 * ms, 4 * ms),
                               ("a", 7 * ms, 8 * ms)],
             "/device:TPU:1": [("a", 1 * ms, 2 * ms)]},
        modules=[("jit__fused_slot_jit(7)", 1 * ms, 4 * ms),
                 ("jit_pairing_check", 7 * ms, 8 * ms)],
        host=[("bench.window", 0, 10 * ms), ("bench.wait", 4 * ms, 7 * ms),
              ("bench.submit", 1 * ms, 1 * ms + 5000)])
    s = trace_reduce.summarize(tr)
    assert s.window_s == pytest.approx(0.010)
    assert s.busy_s == pytest.approx((0.004 + 0.001) / 2)  # mean of devices
    assert s.module_seconds("_fused_slot_jit") == pytest.approx(0.003)
    assert s.module_seconds("pairing_check") == pytest.approx(0.001)
    assert s.device_ops[0] == ["a", pytest.approx(0.003)]
    assert s.idle_gaps == [["bench.wait", pytest.approx(0.003)],
                           ["host", pytest.approx(0.002)],
                           ["host", pytest.approx(0.001)]]


def test_loop_self_time_and_host_clock_moved_to_the_device_clock():
    """A while loop's event holds its body's events; the host annotations
    are 5 s off the device clock."""
    us, off = 1000, 5_000_000_000
    loop = "%while.3 = (s32[]) while(s32[] %t), body=%b"
    body = "%fusion.2 = f32[8] fusion(f32[8] %x), kind=kLoop"
    kern = '%custom-call.1 = f32[8] custom-call(), custom_call_target="tpu_custom_call"'
    tr = trace_reduce.Trace(
        ops={"/device:TPU:0": [(loop, 100 * us, 200 * us),
                               (body, 110 * us, 140 * us),
                               (body, 150 * us, 180 * us),
                               (kern, 300 * us, 320 * us)]},
        modules=[("jit_f(1)", 100 * us, 320 * us)],
        host=[("bench.window", off, off + 400 * us),
              ("bench.submit", off + 90 * us, off + 95 * us),
              ("bench.wait", off + 200 * us, off + 300 * us)])
    s = trace_reduce.summarize(trace_reduce._moved_to_device_clock(tr))
    assert dict(s.device_ops) == {"while.3": pytest.approx(40e-6),
                                  "fusion.2": pytest.approx(60e-6),
                                  "custom-call.1 (mosaic)": pytest.approx(20e-6)}
    assert s.busy_s == pytest.approx(120e-6)
    assert s.window_s == pytest.approx(400e-6)
    assert s.idle_gaps[0] == ["bench.wait", pytest.approx(100e-6)]


def test_programs_stand_for_operations_when_the_op_trace_is_off():
    """With libtpu's per-operation trace off a device plane has programs
    only: they give the busy union, and devices that ran nothing are not
    averaged in."""
    ms = 1_000_000
    tr = trace_reduce.Trace(
        ops={"/device:TPU:0": [("jit__verify_slot_jit(3)", 1 * ms, 5 * ms),
                               ("jit_pairing_check(9)", 6 * ms, 7 * ms)],
             "/device:TPU:1": []},
        modules=[("jit__verify_slot_jit(3)", 1 * ms, 5 * ms),
                 ("jit_pairing_check(9)", 6 * ms, 7 * ms)],
        host=[("bench.window", 0, 10 * ms), ("bench.submit", 0, 9 * ms)])
    s = trace_reduce.summarize(tr)
    assert s.busy_s == pytest.approx(0.005)
    assert s.idle_share == pytest.approx(0.5)
    assert s.module_seconds("_verify_slot_jit") == pytest.approx(0.004)
    assert s.device_ops[0] == ["jit__verify_slot_jit(3)", pytest.approx(0.004)]
    assert [g[0] for g in s.idle_gaps] == ["bench.submit"] * 3


def test_no_device_operations_gives_no_reading():
    s = trace_reduce.summarize(trace_reduce.Trace({}, [], []))
    assert s.busy_s == 0 and s.idle_share is None


def test_a_trace_the_profiler_cut_ends_where_the_device_record_ends():
    """Past the profiler's dropped-buffers mark nothing is recorded: the
    window ends there, and the per-call readers give no reading."""
    ms = 1_000_000
    tr = trace_reduce.Trace(
        ops={"/device:TPU:0": [("jit_pairing_check(1)", 1 * ms, 5 * ms)]},
        modules=[("jit_pairing_check(1)", 1 * ms, 5 * ms)],
        host=[("bench.window", 0, 20 * ms), ("bench.submit", 0, 20 * ms)],
        dropped_from=8 * ms)
    s = trace_reduce.summarize(tr)
    assert s.truncated and s.window_s == pytest.approx(0.008)
    assert s.busy_s == pytest.approx(0.004)
    import run

    read = run.reader("h2c_pairing_device_ms.parsigex", traced=True)

    class W:
        completed = [object()]

    class R:
        trace, window = s, W

    assert read(R) is None
    s.truncated = False
    assert read(R) == pytest.approx(4.0)
