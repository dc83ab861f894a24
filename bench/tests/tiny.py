"""A made-up cell, written as files alone, at a size a CPU test holds."""
import json
import os

CELL = "tiny_host.mix"


def write(root, **correct) -> str:
    """Write ``BENCHMARK.json``, a configuration and a traffic mix for
    ``CELL`` under ``root``; returns ``root``."""
    os.makedirs(os.path.join(root, "bench", "configs"))
    os.makedirs(os.path.join(root, "bench", "traffic"))
    names = ("build_ms", "device_cell_share", "engine_host_ms",
             "trip_waste", "window_compiles", "compile_s",
             "device_trips", "ms_per_trip", "rollup_ms",
             "device_idle_share")
    bench = {
        "configs": [{"name": "tiny_host",
                     "file": "bench/configs/tiny_host.json"}],
        "workloads": [{"name": CELL, "config": "tiny_host",
                       "traffic": "mix", "chips": 1}],
        "end_to_end": [
            {"name": "sim_tasks_per_s", "unit": "tasks/s"},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": n, "unit": "x"} for n in names],
    }
    config = {"cores_per_node": 4, "minutes": 1, "n_functions": 6,
              "invocations_per_min": 20.0, "burst_sigma": 0.55,
              "duration_jitter": 0.08, "zipf_s": 1.1, "horizon_s": 40,
              "traces_per_sweep": 2,
              "policies": {"fifo": {}, "cfs": {},
                           "hybrid": {"n_fifo": 1,
                                      "time_limit_ms": 400.0}},
              "correct": dict({"traffic_diff": 0, "int_diff": 0,
                               "time_dev": 1e-9, "row_dev": 1e-9},
                              **correct)}
    traffic = {"block_seed": 5, "trace_seeds": [{"count": 2, "tasks_min": 1,
                                "tasks_max": 64}],
               "loads": [1.0, 3.0], "policies": ["fifo", "cfs", "hybrid"]}
    for path, obj in (("BENCHMARK.json", bench),
                      ("bench/configs/tiny_host.json", config),
                      ("bench/traffic/mix.json", traffic)):
        with open(os.path.join(root, path), "w") as f:
            json.dump(obj, f)
    return root
