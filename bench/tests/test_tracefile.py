"""The reduction from a profiler trace to the reported numbers, on a
hand-made trace with known answers."""
import pytest
from benchkit import tracefile

MS = 1_000_000  # ns

# Two sweeps, 0-100 ms and 100-200 ms; each builds for 10 ms, runs the
# engine for 80 ms and rolls up for 10 ms. Device operations overlap
# inside the first engine span and leave a gap in the second.
HAND = {
    "devices": 1,
    "spans": sorted([
        [0, 100 * MS, "bench.sweep"], [100 * MS, 100 * MS, "bench.sweep"],
        [0, 10 * MS, "bench.build"], [10 * MS, 80 * MS, "bench.engine"],
        [90 * MS, 10 * MS, "bench.rollup"],
        [100 * MS, 10 * MS, "bench.build"],
        [110 * MS, 80 * MS, "bench.engine"],
        [190 * MS, 10 * MS, "bench.rollup"]]),
    "ops": sorted([
        [20 * MS, 40 * MS, "while"], [30 * MS, 40 * MS, "while"],
        [80 * MS, 1 * MS, "copy"],
        [120 * MS, 30 * MS, "while"], [160 * MS, 20 * MS, "while"]]),
    "modules": [],
}


def test_hand_made_trace():
    tr = dict(HAND)
    assert tracefile.window(tr) == (0, 200 * MS)
    assert tracefile.busy(tr).tolist() == [[20 * MS, 70 * MS], [80 * MS, 81 * MS],
                                  [120 * MS, 150 * MS],
                                  [160 * MS, 180 * MS]]
    busy, win = tracefile.busy_share(tr)
    assert (busy, win) == (101 * MS, 200 * MS)
    assert tracefile.mean_span_ms(tr, "bench.build") == 10.0
    # engine spans: 80 ms each, device busy 51 ms and 50 ms inside
    assert tracefile.host_share_ms(tr, "bench.engine") == \
        pytest.approx(((80 - 51) + (80 - 50)) / 2)
    assert tracefile.top_ops(tr) == [["while", 0.13], ["copy", 0.001]]
    # idle: 0-20, 70-80, 81-120, 150-160 and 180-200 ms, each named by
    # the stage that covers most of it (the first listed on a tie)
    assert tracefile.idle_gaps(tr, k=3) == [
        ["bench.engine", 0.039], ["bench.build", 0.02],
        ["bench.engine", 0.02]]
