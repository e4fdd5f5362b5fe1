"""The control of a cell's correctness check, at the cell's own size.

    JAX_PLATFORMS=cpu python3 benchmark/control.py --workload <cell> \
        --seeds 1 2 3

Runs the cell's set-up, closed loop and reference check with the reference
put in the program's place and one guarantee broken: it verifies one partial
(of a slot: one aggregate) in eight, from a seeded offset, instead of every
one. The check has to find it not correct on every seed; what it misses are
the warm requests' bad partials. Its window is one whole slot (--seconds 0).
One JSON line per seed: correct and the counts compared. The benchmark's own
runs never run this; it needs no chip.
"""

from __future__ import annotations

import argparse
import json
import sys

import run
import systems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args(argv)
    import jax

    cell = run.resolve(run.ROOT, args.workload, traced=False)
    systems.program_system(cell.mix)  # stops on an unknown entry
    bad = 0
    for seed in args.seeds:
        system = systems.ReferenceSystem(control=True, seed=seed)
        res = run.run_cell(cell, seed, args.seconds, False, system,
                           jax.devices(), 1, program=False)
        bad += not res["correct"]
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "attempted": res["attempted"],
                          "checks": res["checks"]}), flush=True)
    return 0 if bad == len(args.seeds) else 1


if __name__ == "__main__":
    sys.exit(main())
