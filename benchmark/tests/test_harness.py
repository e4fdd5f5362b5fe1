"""Discovery by name, adding a cell by data alone, the seeded inputs and the
window-delta reader, all on the CPU without the measurement path."""

import json
import os
import random
import re
import shutil
import subprocess
import sys

import pytest

import inputs
import run
import window
from conftest import CELL, ROOT
from reference import ref

SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_entry_is_found_by_name():
    for c in SPEC["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert c["file"].startswith("benchmark/") and NAME.match(c["name"])
    for w in SPEC["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        for traced in (False, True):
            cell = run.resolve(ROOT, w["name"], traced)
            assert cell.metrics
            for m in cell.metrics:
                assert callable(run.reader(m["name"], traced))
        e2e = {m["name"] for m in run.resolve(ROOT, w["name"], False).metrics}
        assert "setup_s" in e2e and len(e2e) >= 2
    layers = {m["layer"] for m in SPEC["per_layer"]}
    perf = open(os.path.join(ROOT, "PERF.md")).read()
    for layer in layers:
        assert f"| {layer} |" in perf, layer


def test_a_new_config_mix_and_metric_need_no_edit(tmp_path):
    """Copy the benchmark, then add a cell as BENCHMARK.json entries plus new
    files only: a new configuration, a new mix (two duties a slot), a new
    end-to-end metric and a new per-layer reader. Every piece is found by
    name, and the general generator and loop run the new cell."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns(".trace", "__pycache__"))
    spec = json.loads(json.dumps(SPEC))
    old = run.resolve(ROOT, CELL, False)
    cfg = dict(old.config, name="dv1024-4of6", validators=1024)
    (root / "benchmark/configs/dv1024-4of6.json").write_text(json.dumps(cfg))
    mix = dict(old.mix, duties=["prepare_aggregator", "attester"])
    (root / "benchmark/traffic/parsigex-two-duties.json").write_text(
        json.dumps(mix))
    (root / "benchmark/end_to_end/sets_per_s.py").write_text(
        "def read(run):\n"
        "    return len(run.window.completed) / (run.window.end - run.window.t0)\n")
    (root / "benchmark/layers/requests_traced.py").write_text(
        "def read(run):\n    return len(run.window.outcomes)\n")
    new_cell = "parsigex-dv1024-two-duties"
    spec["configs"].append({"name": "dv1024-4of6", "source": "x",
                            "file": "benchmark/configs/dv1024-4of6.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": new_cell, "config": "dv1024-4of6",
                              "traffic": "parsigex-two-duties", "chips": 1,
                              "why": "x"})
    spec["end_to_end"].insert(0, {
        "name": "sets_per_s", "unit": "sets/s", "better": "higher",
        "bound": 0.01, "source": "host_clock", "workloads": [new_cell]})
    spec["per_layer"].append({"name": "requests_traced", "unit": "1",
                              "better": "higher", "source": "host_clock",
                              "layer": "bulk verify", "moves": "sets_per_s",
                              "workloads": [new_cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    code = (
        "import sys; sys.path.insert(0, sys.argv[1] + '/benchmark')\n"
        "import jax, run, systems\n"
        f"t = run.resolve(sys.argv[1], {new_cell!r}, True)\n"
        "names = [m['name'] for m in t.metrics]\n"
        "assert names == ['setup_compile_s', 'setup_trace_s', "
        "'requests_traced'], names\n"
        "for m in t.metrics: run.reader(m['name'], True)\n"
        f"e = run.resolve(sys.argv[1], {new_cell!r}, False)\n"
        "assert [m['name'] for m in e.metrics] == ['sets_per_s', 'setup_s']\n"
        "assert e.config['validators'] == 1024\n"
        "res = run.run_cell(e, 3, 0.01, False, systems.ReferenceSystem(),\n"
        "                   jax.devices(), 1, program=False)\n"
        "assert res['correct'] and res['attempted'] == 10, res\n"
        "assert set(res['metrics']) == {'sets_per_s', 'setup_s'}, res\n"
        "print('ok')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", code, str(root)],
                       capture_output=True, text=True, timeout=300, env=env)
    assert p.stdout.strip().endswith("ok"), p.stderr


def test_a_slot_cell_needs_only_data(tmp_path):
    """The aggregate+verify slot becomes a cell by a workloads entry and an
    end_to_end entry for aggregate_sigs_per_s that names it: the committed
    mix, configuration and readers do the rest."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns(".trace", "__pycache__"))
    spec = json.loads(json.dumps(SPEC))
    new_cell = "sigagg-dv4096-attester"
    spec["workloads"].append({"name": new_cell, "config": "dv4096-4of6",
                              "traffic": "sigagg-attester", "chips": 1,
                              "why": "x"})
    spec["end_to_end"].insert(0, {
        "name": "aggregate_sigs_per_s", "unit": "sigs/s", "better": "higher",
        "bound": 0.01, "source": "host_clock", "workloads": [new_cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    code = (
        "import sys; sys.path.insert(0, sys.argv[1] + '/benchmark')\n"
        "import jax, run, systems\n"
        f"t = run.resolve(sys.argv[1], {new_cell!r}, True)\n"
        "assert [m['name'] for m in t.metrics] == ['setup_compile_s', "
        "'setup_trace_s'], t.metrics\n"
        f"e = run.resolve(sys.argv[1], {new_cell!r}, False)\n"
        "assert [m['name'] for m in e.metrics] == ['aggregate_sigs_per_s', "
        "'setup_s']\n"
        "assert systems.program_system(e.mix) is systems.SlotSystem\n"
        "e.config['validators'] = 2048\n"
        "e.mix['pool_slots'] = 1\n"
        "res = run.run_cell(e, 3, 0.0, False, systems.ReferenceSystem(),\n"
        "                   jax.devices(), 1, program=False)\n"
        "assert res['correct'] and res['attempted'] == 1, res\n"
        "assert set(res['metrics']) == {'aggregate_sigs_per_s', 'setup_s'}\n"
        "print('ok')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", code, str(root)],
                       capture_output=True, text=True, timeout=300, env=env)
    assert p.stdout.strip().endswith("ok"), p.stderr


def test_run_with_an_unknown_entry_stops_before_set_up(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns(".trace", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    mix = root / "benchmark/traffic/parsigex-attester.json"
    mix.write_text(json.dumps(dict(json.loads(mix.read_text()),
                                   entry="tbls.nope")))
    p = subprocess.run(
        [sys.executable, str(root / "benchmark/run.py"), "--workload", CELL,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "entry 'tbls.nope'" in p.stderr and "no TPU" not in p.stderr


def _gen(seed, validators=256, mix_name=None, **mix):
    cell = run.resolve(ROOT, CELL, False)
    config = dict(cell.config, validators=validators)
    base = cell.mix if mix_name is None else run.read_json(os.path.join(
        ROOT, "benchmark", "traffic", f"{mix_name}.json"))
    return inputs.Generator(config, dict(base, **mix), seed)


# sha256 of the peer sets (warm and pool) that the parsigex mix's generator
# made for these seeds and validator counts before the generator learnt to
# make slots: the verify cell's requests must stay byte for byte the same
VERIFY_SETS = {
    (2**31 + 11, 256):
        "dd109a1013863c67a404ab80c05bf69d4c6d22ee465328375482852f81269226",
    (5, 2048):
        "9dddb1e6bcda5c0b0bcebff0d6c86ba8ef67fac8f7b021fb19bb32d65c510b4d",
    (2**31 + 5, 4096):
        "5e5a3c8bb3cb9b307b2be95141f85b1e85872e7f5e98dd5ffa184979c36bc753",
}


@pytest.mark.parametrize("seed,validators", sorted(VERIFY_SETS))
def test_verify_sets_are_byte_identical(seed, validators):
    import hashlib

    g = _gen(seed, validators)
    h = hashlib.sha256()
    for r in g.warm() + g.pool():
        h.update(repr((r.index, r.slot, r.duty, r.peer, r.pks, r.roots,
                       r.sigs, r.secrets, r.bad, r.last_of_slot)).encode())
    assert h.hexdigest() == VERIFY_SETS[(seed, validators)]


def test_slot_partials_combine_to_the_group_signature():
    """Each slot's chosen partials Lagrange-combine to [secret]H(root) for
    every validator whose partials are all over the root; the bad ones sit
    in the warm slots' second halves; the id sets are the node's own share
    and threshold - 1 peers, vary across slots and are fixed by the seed."""
    a = _gen(2**31 + 3, mix_name="sigagg-attester", pool_slots=6)
    ra = a.warm() + a.pool()
    assert [r.bad for r in ra] == [1, 1, 1] + [0] * 7
    assert all(r.last_of_slot and r.size == 256 // 32 for r in ra)
    assert len({r.slot for r in ra}) == len(ra)
    assert len({r.roots[0] for r in ra}) == len(ra)        # fresh roots
    for r in ra:
        assert len(r.ids) == 4 and 1 in r.ids
        assert all(sorted(b) == list(r.ids) for b in r.partials)
        assert r.pks == [ref.pubkey(k) for k in r.secrets]
        lam = ref.lagrange_at_zero(list(r.ids))
        good = []
        for v, (b, k, m) in enumerate(zip(r.partials, r.secrets, r.roots)):
            agg = ref.g2_compress(ref.lincomb(
                ref.Fp2, [ref.g2_decompress(b[i]) for i in r.ids], lam))
            good.append(agg == ref.sign(k, m))
        assert good.count(False) == r.bad
        assert all(good[:r.size // 2])
    assert len({r.ids for r in ra}) > 1
    b, c = (_gen(s, mix_name="sigagg-attester", pool_slots=6)
            for s in (2**31 + 3, 7))
    rb, rc = b.warm() + b.pool(), c.warm() + c.pool()
    key = (lambda r: (r.ids, r.pks, r.roots, r.partials, r.bad))
    assert [key(r) for r in ra] == [key(r) for r in rb]
    assert [r.ids for r in ra] != [r.ids for r in rc]
    assert [(r.size, r.bad) for r in ra] == [(r.size, r.bad) for r in rc]


def _key(r):
    return (r.slot, r.duty, r.peer, r.pks, r.roots, r.sigs, r.bad,
            r.last_of_slot)


def test_inputs_same_sizes_any_seed_same_seed_same_inputs():
    a, b, c = _gen(2**31 + 11), _gen(2**31 + 11), _gen(5)
    ra, rb, rc = (g.warm() + g.pool() for g in (a, b, c))
    assert [_key(r) for r in ra] == [_key(r) for r in rb]
    assert [_key(r) for r in ra] != [_key(r) for r in rc]
    shape = (lambda r: (r.size, r.bad, r.last_of_slot, len(set(r.roots))))
    assert [shape(r) for r in ra] == [shape(r) for r in rc]


def test_sets_come_as_slots_of_the_duty_schedule():
    g = _gen(3, validators=256)
    warm, pool = g.warm(), g.pool()
    assert [r.bad for r in warm] == [1, 1, 1, 0] and warm[-1].last_of_slot
    for r in warm:
        bad = [i for i, (s, k, m) in enumerate(zip(r.sigs, r.secrets,
                                                   r.roots))
               if not ref.verifies(s, k, m)]
        assert len(bad) == r.bad and all(i >= r.size // 2 for i in bad)
    first = warm[0]
    assert len(pool) == 5 * int(g.mix["pool_slots"])
    roots = set()
    for slot in (pool[:5], pool[5:10]):
        assert [r.last_of_slot for r in slot] == [False] * 4 + [True]
        assert len({r.slot for r in slot}) == 1
        assert sorted(r.peer for r in slot) == [2, 3, 4, 5, 6]   # one set a peer
        assert len({r.roots[0] for r in slot}) == 1                # one root
        assert all(len(set(r.roots)) == 1 and r.size == 256 // 32
                   and r.bad == 0 for r in slot)
        roots.add(slot[0].roots[0])
    assert len(roots) == 2 and first.roots[0] not in roots        # fresh roots
    # every validator attests once an epoch
    seen = [v for s in g.attesters for v in s]
    assert sorted(seen) == list(range(256))


def test_window_runs_to_the_end_of_the_slot():
    from types import SimpleNamespace

    import load
    import systems

    reqs = [SimpleNamespace(size=1, last_of_slot=i % 5 == 4, index=i)
            for i in range(15)]
    sys_ = SimpleNamespace(submit=lambda a: systems._resolved(lambda: True))
    win = load.closed_loop(sys_, [(r, None) for r in reqs], 1, 0.0)
    assert len(win.outcomes) == 5 and win.rate() > 0
    win = load.closed_loop(sys_, [(r, None) for r in reqs], 1, 0.0,
                           max_requests=2)
    assert len(win.outcomes) == 2
    with pytest.raises(load.PoolExhausted):
        load.closed_loop(sys_, [(r, None) for r in reqs[:3]], 1, 0.0)


def test_closed_loop_waits_for_answers_that_come_later():
    """The program's slot entry answers from a worker thread: the warm-up's
    endless loop and the window both wait for such futures."""
    import math
    import threading
    from concurrent.futures import Future
    from types import SimpleNamespace

    import load

    def submit(_args):
        fut = Future()
        threading.Timer(0.02, fut.set_result, (True,)).start()
        return fut

    reqs = [SimpleNamespace(size=1, last_of_slot=i % 2 == 1, index=i)
            for i in range(8)]
    sys_ = SimpleNamespace(submit=submit)
    win = load.closed_loop(sys_, [(r, None) for r in reqs], 1, math.inf,
                           max_requests=3, late_wait=5.0)
    assert [o.answer for o in win.outcomes] == [True] * 3
    win = load.closed_loop(sys_, [(r, None) for r in reqs], 1, 0.03)
    assert len(win.completed) == 2 and win.rate() > 0


def test_cluster_shares_interpolate_to_the_group_key():
    from reference import ref

    cl = inputs.Cluster(random.Random(1), 3, 6, 4)
    for secret, shares in zip(cl.secrets, cl.shares):
        ids = [2, 3, 5, 6]
        lam = ref.lagrange_at_zero(ids)
        assert sum(l * shares[i] for l, i in zip(lam, ids)) % ref.R == secret


def test_window_delta_reads_exact_sums_over_the_window():
    from charon_tpu.utils.metrics import Registry

    reg = Registry()
    h = reg.histogram("ops_device_dispatch_seconds", "", ("phase",),
                      buckets=(1, 10))
    c = reg.counter("ops_pairing_total", "", ("path",))
    h.observe(40.0, "verify")
    c.inc("device", amount=3)
    before = window.snapshot(reg)
    for v in (12.5, 17.5):
        h.observe(v, "verify")
    h.observe(0.25, "pack")
    c.inc("device", amount=2)
    c.inc("native")
    d = window.Delta(before, window.snapshot(reg))
    assert d.hist("ops_device_dispatch_seconds", phase="verify") == (30.0, 2)
    assert d.mean("ops_device_dispatch_seconds", phase="verify") == 15.0
    assert d.mean("ops_device_dispatch_seconds", phase="pack") == 0.25
    assert d.mean("ops_device_dispatch_seconds", phase="drain") is None
    assert d.counter("ops_pairing_total", path="device") == 2
    assert d.counter("ops_pairing_total") == 3


def test_compile_seconds_are_the_union_of_nested_events():
    """A function traced inside another reports its own duration within
    its caller's; set-up seconds count that time once."""
    log = window.CompileLog()
    log.intervals += [("setup", window.TRACE_EVENT, 0.0, 10.0),   # outer
                      ("setup", window.TRACE_EVENT, 2.0, 5.0),    # nested
                      ("setup", window.LOWER_EVENT, 10.0, 14.0),
                      ("setup", window.COMPILE_EVENT, 20.0, 21.5),
                      ("window", window.TRACE_EVENT, 30.0, 31.0)]
    assert log.total("setup", window.TRACE_EVENT, window.LOWER_EVENT) == 14.0
    assert log.total("setup", window.COMPILE_EVENT) == 1.5
    assert log.total("window", window.TRACE_EVENT) == 1.0
    assert log.total("after", window.TRACE_EVENT) == 0.0
