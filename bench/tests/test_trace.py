"""The trace reduction, on a trace recorded on a TPU v5e from a toy run of
the pjit cell (``data/toy_pjit.xplane.pb``: ``run_cell(toy_cell(...), 12345,
0.05, True, ..., keep_trace=<dir>)``, a 50 ms window), and on intervals made
by hand."""
from pathlib import Path

import numpy as np
import pytest

from bench import trace as tr

DATA = Path(__file__).parent / "data" / "toy_pjit.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    return tr.Trace(str(DATA))


def test_recorded_trace_has_one_chip_and_the_window(recorded):
    assert list(recorded.chips) == [0]
    assert 0.05 <= recorded.window_s() < 0.2
    names = {n for n, _, _ in recorded.spans}
    assert names == {"bench.step", "bench.materialize"}


def test_busy_and_idle_gaps_partition_the_window(recorded):
    busy = recorded.busy_s()[0]
    idle = sum(t for _, t in recorded.idle_gaps())
    assert 0 < busy < recorded.window_s()
    assert busy + idle == pytest.approx(recorded.window_s(), rel=1e-9)
    assert {n for n, _ in recorded.idle_gaps()} <= {
        "bench.step", "bench.materialize", "host"}


def test_top_ops_count_self_time_once(recorded):
    ops = recorded.op_seconds()
    assert 0 < sum(ops.values()) <= recorded.busy_s()[0] * (1 + 1e-9)
    top = recorded.breakdown()["device_ops"]
    assert len(top) == 10
    assert [t for _, t in top] == sorted((t for _, t in top), reverse=True)
    assert all(not n.startswith("%while") for n, _ in top[:3])


def test_one_chip_has_no_collective_time(recorded):
    assert recorded.collective_exposed_s() == {0: 0.0}


def test_exposed_collective_is_what_no_other_op_covers():
    coll = tr.union(np.array([[0.0, 4.0], [6.0, 7.0]]))
    other = tr.union(np.array([[1.0, 2.0], [3.0, 6.5]]))
    exposed = tr.subtract(coll, other)
    assert exposed.tolist() == [[0.0, 1.0], [2.0, 3.0], [6.5, 7.0]]
    assert tr.length(exposed) == pytest.approx(2.5)


def test_self_time_of_nested_events():
    # a loop [0, 10] holding [1, 3] and [4, 9], the latter holding [5, 6]
    iv = np.array([[0.0, 10.0], [1.0, 3.0], [4.0, 9.0], [5.0, 6.0],
                   [11.0, 12.0]])
    assert tr.self_times(iv).tolist() == [3.0, 2.0, 4.0, 1.0, 1.0]
