"""Trip arithmetic on fixed counter arrays."""
import pytest
from benchkit import counters

# (B, max n_iters, sum n_iters) per program
PROGRAMS = [(132, 67, 3620), (60, 82, 1995)]


def test_device_trips():
    assert counters.device_trips(PROGRAMS) == 67 + 82
    assert counters.device_trips([]) == 0


def test_trip_waste():
    ran = 132 * 67 + 60 * 82
    assert counters.trip_waste(PROGRAMS) == pytest.approx(
        1 - (3620 + 1995) / ran, rel=1e-15)
    # every cell needed as many trips as the batch ran: no waste
    assert counters.trip_waste([(4, 10, 40)]) == 0.0
    assert counters.trip_waste([]) is None
