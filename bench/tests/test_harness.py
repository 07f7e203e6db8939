"""The harness finds every piece by name, refuses to run without a TPU, and
at a toy size on the CPU prints a well-formed last line for every cell."""
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import pytest

from bench.cell import BENCH_DIR, CHECKOUT, load_benchmark, load_cell
from bench.correctness import load_limits
from bench.run import load_metric, run_cell
from bench.tests.toy import toy_cell

BENCH = load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def test_every_name_resolves():
    for c in BENCH["configs"]:
        assert (CHECKOUT / c["file"]).is_file()
    for name in CELLS:
        cell = load_cell(name)
        assert cell.end_to_end and cell.per_layer
        assert set(load_limits(cell)) >= {"loss_gap", "grad_gap",
                                          "delta_gap", "frozen_changed"}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(load_metric(m["name"]))


def _run(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "3000000019", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_result():
    p = _run(CHECKOUT)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_bare_directory_exits_nonzero(tmp_path):
    shutil.copy(CHECKOUT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("name", CELLS)
def test_toy_rehearsal_prints_a_well_formed_line(name, trace):
    cell = toy_cell(name)
    out = run_cell(cell, 2 ** 31 + 7, 0.5, bool(trace),
                   jax.devices()[:cell.chips], limits=load_limits(cell),
                   t_start=time.perf_counter())
    line = json.loads(json.dumps(out))
    assert list(line)[:5] == RESULT_KEYS and list(line)[-1] == "compared"
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert line["device"]["count"] == cell.chips
    want = {m["name"] for m in (cell.per_layer if trace else cell.end_to_end)}
    assert set(line["metrics"]) <= want
    if not trace:                 # host-clock metrics exist on the CPU too
        assert set(line["metrics"]) == want
    for v in line["compared"].values():
        assert set(v) == {"value", "limit"}
