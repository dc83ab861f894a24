"""In a directory that holds only ``BENCHMARK.json`` and the benchmark's
own files, a run prints no result and exits non-zero."""
import os
import shutil
import subprocess
import sys

from benchkit import spec


def test_bare_directory_gives_no_result(tmp_path):
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(spec.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "ep3_node.load_sweep", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
