"""The comparison that decides `correct`, driven through the harness's own
run (set-up, closed loop, check) with the reference in the program's place,
at a size a CPU test holds, for each entry a mix can drive: a peer set's
bulk verify (parsigex-attester) and a slot's aggregate+verify
(sigagg-attester).

* sound: the reference itself comes out correct;
* control: the reference verifying one partial (one aggregate) in eight,
  the guarantee that every partial is verified broken, comes out not
  correct;
* faults: a path that returns the previous request's answer, leaves out
  half of it, or alters an answer where it is made comes out not correct.

The control at the cell's own size runs on the chip host with
`python3 benchmark/control.py`.
"""

import json
import os

import jax
import pytest

import run
import systems
from conftest import CELL, ROOT

MIXES = ("parsigex-attester", "sigagg-attester")


def tiny(mix: str = "parsigex-attester") -> run.Cell:
    """The committed cell's configuration with 2048 validators (64 partials
    a set, 64 aggregates a slot) under `mix`, with one pool slot: the
    window of a `--seconds 0` run sends one whole slot."""
    cell = run.resolve(ROOT, CELL, traced=False)
    cell.config = dict(cell.config, validators=2048)
    cell.mix = dict(run.read_json(os.path.join(
        ROOT, "benchmark", "traffic", f"{mix}.json")), pool_slots=1)
    return cell


def drive(system, mix: str = "parsigex-attester",
          seed: int = 2**31 + 7) -> dict:
    return run.run_cell(tiny(mix), seed=seed, seconds=0.0, traced=False,
                        system=system, devices=jax.devices(), chips=1,
                        program=False)


@pytest.mark.parametrize("mix,requests,checks", [
    ("parsigex-attester", 5, ["unanswered", "verdict_mismatches"]),
    ("sigagg-attester", 1,
     ["unanswered", "verdict_mismatches", "aggregate_mismatches"])])
def test_reference_is_correct(mix, requests, checks):
    res = drive(systems.ReferenceSystem(), mix)
    assert res["correct"], res["checks"]
    assert res["attempted"] == requests and res["failed"] == 0  # one slot
    assert list(res)[-1] == "checks" and list(res["checks"]) == checks
    json.dumps(res)


@pytest.mark.parametrize("mix", MIXES)
@pytest.mark.parametrize("seed", (5, 2**31 + 5))
def test_control_is_not_correct(seed, mix):
    res = drive(systems.ReferenceSystem(control=True, seed=seed), mix,
                seed=seed)
    assert not res["correct"]
    assert res["checks"]["verdict_mismatches"]["value"] > 0


@pytest.mark.parametrize("mix", MIXES)
@pytest.mark.parametrize("fault", ("stale", "half", "altered"))
def test_fault_is_not_correct(fault, mix):
    res = drive(systems.ReferenceSystem(fault=fault), mix)
    assert not res["correct"], (fault, res["checks"])
    if mix == "sigagg-attester":  # wrong aggregates, the valid slot's too
        assert res["checks"]["aggregate_mismatches"]["value"] > 0


def test_program_entry_on_the_native_backend_is_correct():
    """The program's own slot entry (tbls.threshold_aggregate_verify_submit,
    here on the facade's CPU backend), driven as the chip run drives it,
    answers correctly: the arguments are built as core/sigagg builds them."""
    from charon_tpu import tbls

    cls = systems.program_system(tiny("sigagg-attester").mix)
    system = cls.__new__(cls)  # the backend the test process has, not tpu_bls
    system.tbls = tbls
    res = drive(system, "sigagg-attester")
    assert res["correct"] and res["attempted"] == 1, res["checks"]


def test_each_mix_picks_its_system():
    assert systems.program_system(tiny().mix) is systems.ProgramSystem
    assert (systems.program_system(tiny("sigagg-attester").mix)
            is systems.SlotSystem)


def test_run_without_tpu_prints_no_result():
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
