"""The comparison that decides `correct`, driven through the harness's own
run (set-up, closed loop, check) with the reference in the program's place,
at a size a CPU test holds.

* sound: the reference itself comes out correct;
* control: the reference verifying one partial in eight (the guarantee that
  every partial is verified broken) comes out not correct;
* faults: a path that returns the previous set's answer, leaves out half the
  set, or alters an answer where it is made comes out not correct.

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


def tiny() -> run.Cell:
    """The cell as committed, with 64 partials a set (2048 validators)."""
    cell = run.resolve(ROOT, CELL, traced=False)
    cell.config = dict(cell.config, validators=2048)
    return cell


def drive(system, seed: int = 2**31 + 7) -> dict:
    return run.run_cell(tiny(), seed=seed, seconds=0.0, traced=False,
                        system=system, devices=jax.devices(), chips=1,
                        program=False)


def test_reference_is_correct():
    res = drive(systems.ReferenceSystem())
    assert res["correct"], res["checks"]
    assert res["attempted"] == 5 and res["failed"] == 0  # one whole slot
    assert list(res)[-1] == "checks"
    json.dumps(res)


@pytest.mark.parametrize("seed", (5, 2**31 + 5))
def test_control_is_not_correct(seed):
    res = drive(systems.ReferenceSystem(control=True, seed=seed), seed=seed)
    assert not res["correct"]
    assert res["checks"]["verdict_mismatches"]["value"] > 0


@pytest.mark.parametrize("fault", ("stale", "half", "altered"))
def test_fault_is_not_correct(fault):
    res = drive(systems.ReferenceSystem(fault=fault))
    assert not res["correct"], (fault, res["checks"])


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
