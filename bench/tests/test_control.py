"""The control: the plain reference computed in float32, one precision
below the float64 the configurations state, put in the program's place,
fails the comparison under each configuration's own limits, while the
program (XLA:CPU, bit-identical there) passes it. Small traffic of the
real configurations."""
import json
import os

import numpy as np
import pytest
from benchkit import check, runner, spec, traffic

SMALL = {
    # the paper host's first second of arrivals (about 350 tasks)
    "paper_host": ({"horizon_s": 1, "traces_per_sweep": 1},
                   {"block_seed": 5, "trace_seeds": [{"count": 1, "tasks_min": 1,
                                     "tasks_max": 512}],
                    "loads": [1.0], "policies": ["fifo", "cfs", "hybrid"]}),
    "ep3_node": ({"traces_per_sweep": 2},
                 {"block_seed": 5, "trace_seeds": [{"count": 2, "tasks_min": 1,
                                   "tasks_max": 64}],
                  "loads": [1.0, 6.0],
                  "policies": ["fifo", "cfs", "hybrid"]}),
}


@pytest.mark.parametrize("name", sorted(SMALL))
def test_control_fails_and_program_passes(name, tmp_path):
    import jax
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    with open(os.path.join(spec.BENCH_DIR, "configs",
                           f"{name}.json")) as f:
        config = json.load(f)
    config.update(SMALL[name][0])
    plan = traffic.plan(config, SMALL[name][1], 2 ** 33 + 7)
    sw = runner.run_sweep(config, plan)
    lim = check.limits(config)

    def judged(answer):
        got = check.worst([check.compare(answer(k, c),
                                         check.reference(config, plan, c))
                           for k, c in enumerate(plan.cells)], 0)
        return check.judge(got, lim), got

    ok, got = judged(lambda k, c: check.observed(sw.results[k]))
    assert ok, got
    ok, got = judged(lambda k, c: check.reference(config, plan, c,
                                                  dtype=np.float32))
    assert not ok, got
