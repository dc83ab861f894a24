"""Readings that set a cell's correctness limits.

For each seed, the sweep of the trace block that seed draws (in place
of the traffic file's ``block_seed``) runs through the program on the
device, and every cell of it is compared with the plain reference
(``benchkit.check``): these are the program's readings, the lower end
of each limit. For the control seeds, the reference itself, computed in
float32 (the precision below the float64 the configuration states),
takes the program's place: its readings are the upper end. Run on the
chip, one process, sweeps of one shape compiled once::

    python3 bench/control.py --workload ep3_node.load_sweep \
        --seeds 1,2,3,4,5,6,7,8,9,10,11,12 --control-seeds 1,2,3

Prints one JSON line per seed and side.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))


def readings(cell, plan_, answer) -> dict:
    """Worst reading of each number over the plan's cells; ``answer``
    gives a cell's answer in ``check.observed`` form."""
    from benchkit import check
    per_cell = [check.compare(answer(k, c),
                              check.reference(cell.config, plan_, c))
                for k, c in enumerate(plan_.cells)]
    return check.worst(per_cell, 0)


def main() -> int:
    import numpy as np

    from benchkit import check, runner, spec, traffic

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    a = ap.parse_args()
    cell = spec.load_cell(a.workload)
    device = runner._device(cell.chips, True)
    import jax
    jax.config.update("jax_compilation_cache_dir", runner.CACHE_DIR)
    control = {int(s) for s in a.control_seeds.split(",") if s}
    for seed in [int(s) for s in a.seeds.split(",")]:
        plan_ = traffic.plan(cell.config, cell.traffic, seed,
                             block_seed=seed)
        t0 = time.perf_counter()
        sw = runner.run_sweep(cell.config, plan_)
        got = readings(cell, plan_,
                       lambda k, c: check.observed(sw.results[k]))
        print(json.dumps({"seed": seed, "side": "program",
                          "readings": got, "failed": sw.failed,
                          "programs": sw.programs, "device": device,
                          "s": time.perf_counter() - t0}), flush=True)
        if seed in control:
            t0 = time.perf_counter()
            got = readings(cell, plan_, lambda k, c: check.reference(
                cell.config, plan_, c, dtype=np.float32))
            print(json.dumps({"seed": seed, "side": "control",
                              "readings": got,
                              "s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
