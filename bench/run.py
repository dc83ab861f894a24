"""Run one cell of the benchmark that ``BENCHMARK.json`` defines.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Runs on the machine it is started on and needs a TPU: without one, or
with fewer chips than the cell asks for, it prints no result and exits
with code 2. The last line of standard output is the result as one JSON
object; the numbers the check compared, each with its limit, are the
last lines of standard error.
"""
import os
import sys
import time

T_START = time.time()

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

if __name__ == "__main__":
    from benchkit.runner import main
    sys.exit(main(t_start=T_START))
