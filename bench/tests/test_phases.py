"""The phase reduction (``bench/phases.py``): on intervals made by hand, on
an older trace recorded on a TPU v5e before the program named its phases
(``data/toy_pjit.xplane.pb``: the reduction finds nothing there, and
``bench/trace.py`` reads what it read before), and on a toy trace of the
program that names them (``data/toy_phases.xplane.pb``, recorded on a TPU
v5e: the toy pjit cell, a 30 ms window)."""
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import phases as ph
from bench import trace as tr
from bench.correctness import load_limits
from bench.run import run_cell
from bench.tests.toy import toy_cell

DATA = Path(__file__).parent / "data"


def made(window, spans, chips):
    """A PhaseTrace from hand-made intervals: ``chips`` maps a chip to
    ``(ops [[start, end]], phases, attention flags, modules)``."""
    pt = object.__new__(ph.PhaseTrace)
    pt.window = window
    pt.spans = {k: sorted(v) for k, v in spans.items()}
    pt.spans.setdefault(ph.ROUND_SPAN, [])
    for k in ph.IDLE_SPANS:
        pt.spans.setdefault(k, [])
    pt.chips = {}
    for c, (ops, phase, attn, modules) in chips.items():
        iv = np.asarray(ops, float)
        pt.chips[c] = {"ops": iv, "self": tr.self_times(iv), "phase": phase,
                       "attention": np.asarray(attn, bool),
                       "modules": modules}
    return pt


@pytest.mark.parametrize("op_name, phase", [
    ("jit(f)/jvp(ringada.trunk)/while/body/ringada.attention/dot_general:",
     "trunk"),
    ("jit(f)/transpose(jvp(ringada.hot))/while/body/mul", "hot"),
    ("jit(f)/ringada.hot/ringada.head/reduce_max", "head"),
    ("jit(fused)/ringada.optimizer/sqrt", "optimizer"),
    ("jit(f)/ringada.attention/le", None),
    ("", None),
])
def test_innermost_phase_scope_wins(op_name, phase):
    assert ph.phase_of(op_name) == phase


def test_phase_time_counts_self_time_of_ops_starting_in_the_window():
    # a loop [1, 9] (no scope) holds a trunk op [1, 4] and a hot op [5, 8];
    # a head op [9, 10]; an optimizer op starts after the window
    ops = [[1, 9], [1, 4], [5, 8], [9, 10], [12, 13]]
    phase = [None, "trunk", "hot", "head", "optimizer"]
    attn = [False, True, True, False, False]
    pt = made((0.0, 11.0), {ph.ROUND_SPAN: [(0, 5), (5, 10)]},
              {0: (ops, phase, attn, [])})
    r = pt.readings()
    assert r["rounds"] == 2
    assert r["trunk_ms_per_round"] == pytest.approx(1e3 * 3 / 2)
    assert r["hot_ms_per_round"] == pytest.approx(1e3 * 3 / 2)
    assert r["head_ms_per_round"] == pytest.approx(1e3 * 1 / 2)
    assert r["optimizer_ms_per_round"] == 0
    assert r["attention_ms_per_round"] == pytest.approx(1e3 * 6 / 2)
    # the loop's own time (8 - 6 nested = 2 s) is unscoped, of 9 s in all
    assert r["unscoped_busy_pct"] == pytest.approx(100 * 2 / 9)


def test_idle_is_what_each_host_span_covers_of_the_gaps():
    # busy [1, 3] and [4, 8] in a window [0, 10]: gaps [0, 1] [3, 4] [8, 10]
    spans = {ph.ROUND_SPAN: [(0, 2), (2.5, 5)],
             "ringada.data": [(0, 0.5)], "ringada.dispatch": [(0.5, 1.5)],
             "ringada.sync": [(3.5, 4.5), (8.5, 9), (11, 12)]}
    chip = ([[1, 3], [4, 8]], ["hot", "hot"], [False, False], [])
    pt = made((0.0, 10.0), spans, {0: chip, 1: chip})
    r = pt.readings()
    assert r["idle_data_ms_per_round"] == pytest.approx(1e3 * 0.5 / 2)
    assert r["idle_dispatch_ms_per_round"] == pytest.approx(1e3 * 0.5 / 2)
    assert r["idle_sync_ms_per_round"] == pytest.approx(1e3 * 1.0 / 2)
    assert r["idle_ms_per_round"] == pytest.approx(1e3 * 4.0 / 2)


def test_clocks_pair_rounds_with_runs_of_the_main_executable():
    spans = {"ringada.dispatch": [(0, 1), (10, 11)],
             "ringada.sync": [(1, 9), (11, 15)]}
    modules = [("jit_step(1)", 0.5, 8.0),          # round 0: in order
               ("jit_small(2)", 8.5, 8.6),         # not the main executable
               ("jit_step(1)", 10.5, 16.0)]        # round 1: ends after sync
    pt = made((0.0, 20.0), spans, {0: ([[0.5, 8.0]], ["hot"], [False],
                                       modules)})
    assert pt.clocks() == {"rounds": 2, "module_runs": 2, "pairs": 2,
                           "dispatch_first": 2, "synced_after": 1,
                           "lead_ms_min": pytest.approx(500.0),
                           "lag_ms_min": pytest.approx(-1000.0)}


@pytest.fixture(scope="module")
def old():
    return ph.PhaseTrace(str(DATA / "toy_pjit.xplane.pb"))


def test_old_trace_reads_as_bench_trace_reads_it(old):
    ref = tr.Trace(str(DATA / "toy_pjit.xplane.pb"))
    assert old.window == ref.window
    assert list(old.chips) == list(ref.chips) == [0]
    np.testing.assert_array_equal(old.chips[0]["ops"], ref.chips[0]["ops"])
    np.testing.assert_array_equal(old.chips[0]["self"], ref.chips[0]["self"])


def test_old_trace_has_op_names_but_no_phase(old):
    """The program before its scopes: ops carry ``op_name``s, none has a
    phase, and with no ``ringada.round`` span there is nothing to read."""
    assert old.rounds() == 0 and old.readings() is None
    busy = old.self_seconds()
    assert busy["all"] > 0 and busy["unscoped"] == busy["all"]


def test_bench_trace_readings_of_the_old_trace_are_unchanged():
    ref = tr.Trace(str(DATA / "toy_pjit.xplane.pb"))
    assert ref.window_s() == pytest.approx(0.052247059, rel=1e-12)
    assert ref.busy_s()[0] == pytest.approx(0.000274352, rel=1e-9)
    top = ref.breakdown(3)
    assert [n for n, _ in top["device_ops"]] == [
        "%fusion.1", "%convolution_add_fusion.4", "%fusion.398"]
    assert [n for n, _ in top["idle_gaps"]] == ["bench.materialize"] * 3
    assert top["idle_gaps"][0][1] == pytest.approx(0.004695621, rel=1e-6)


def test_rounds_equal_the_window_calls_on_the_cpu(tmp_path):
    """A toy window on the CPU: one ``ringada.round`` span per call (the CPU
    trace has no TPU plane, so there are no device readings)."""
    cell = toy_cell("stablelm3b-pjit-depth1")
    out = run_cell(cell, 2 ** 31 + 11, 0.2, True, jax.devices()[:1],
                   limits=load_limits(cell), keep_trace=str(tmp_path),
                   t_start=time.perf_counter())
    pt = ph.PhaseTrace(str(next(tmp_path.rglob("*.xplane.pb"))))
    assert pt.rounds() == out["attempted"] >= 1
    assert pt.readings() is None


@pytest.fixture(scope="module")
def named():
    return ph.PhaseTrace(str(DATA / "toy_phases.xplane.pb"))


def test_named_trace_reads_every_phase(named):
    r = named.readings()
    bench_steps = [s for n, s, _ in tr.Trace(
        str(DATA / "toy_phases.xplane.pb")).spans if n == "bench.step"]
    assert r["rounds"] == len(bench_steps)
    for key in ("trunk", "hot", "head", "optimizer", "attention"):
        assert r[f"{key}_ms_per_round"] > 0, key
    busy = named.self_seconds()
    parts = sum(busy[k] for k in ("trunk", "hot", "head", "optimizer",
                                  "unscoped"))
    assert parts == pytest.approx(busy["all"], rel=1e-9)
    assert r["attention_ms_per_round"] < r["trunk_ms_per_round"] + \
        r["hot_ms_per_round"]


def test_named_trace_idle_spans_do_not_overlap(named):
    r = named.readings()
    spans = sum(r[f"idle_{k}_ms_per_round"] for k in ("data", "dispatch",
                                                       "sync"))
    assert 0 < spans <= r["idle_ms_per_round"] * (1 + 1e-9)
    clocks = r["clocks"]
    assert clocks["pairs"] == clocks["rounds"] == r["rounds"]
    assert clocks["synced_after"] == clocks["rounds"]
