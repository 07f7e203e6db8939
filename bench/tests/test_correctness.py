"""The comparison fails what it must fail, at a toy size on the CPU: the
reference in float8 put in the program's place, and the program driven
through a whole run with its timed path broken underneath.  Each must push
a number over its limit, and to 3 times (the control, a state left
unchanged) or 10 times (half of the batch) the unbroken toy run's."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench import correctness as cx
from bench.cell import load_benchmark
from bench.correctness import load_limits
from bench.run import run_cell, set_up
from bench.tests.toy import toy_cell

CELLS = [w["name"] for w in load_benchmark()["workloads"]]
SEED = 2 ** 33 + 5


def _run(cell):
    return run_cell(cell, SEED, 0.3, False, jax.devices()[:cell.chips],
                    limits=load_limits(cell), t_start=time.perf_counter())


@pytest.fixture(scope="module")
def sound():
    """Each cell's unbroken toy run: what a fault has to move."""
    return {name: _run(toy_cell(name))["compared"] for name in CELLS}


def tripped(shown, sound_shown, factor):
    """Numbers over their limit that read at least ``factor`` times the
    unbroken toy run's (the toy's own rounding is not the chip's, so its
    sound readings are not held to the chip's limits)."""
    return [k for k, v in shown.items()
            if v["limit"] is not None and not v["value"] <= v["limit"]
            and v["value"] >= factor * sound_shown[k]["value"]]


@pytest.mark.parametrize("name", CELLS)
def test_float8_control_is_not_correct(name, sound):
    cell = toy_cell(name)
    devices = jax.devices()[:cell.chips]
    _, _, feed = set_up(cell, SEED, devices)
    ref = cx.reference_readings(cell, SEED, feed.history, devices)
    ctl = cx.reference_readings(cell, SEED, feed.history, devices,
                                control=True)
    ok, shown = cx.verdict(cx.numbers({**ctl, "frozen_changed": 0}, ref),
                           load_limits(cell))
    assert not ok and tripped(shown, sound[name], 3), shown


@pytest.mark.parametrize("name", CELLS)
def test_step_that_returns_its_state_unchanged_is_not_correct(name, sound,
                                                              monkeypatch):
    from repro.optim import adamw

    monkeypatch.setattr(adamw, "leaf_update",
                        lambda g, m, v, p, **kw: (m, v, p))
    out = _run(toy_cell(name))
    assert not out["correct"] and tripped(out["compared"], sound[name], 3)
    assert out["compared"]["delta_gap"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("name", CELLS)
def test_half_of_the_batch_left_out_is_not_correct(name, sound, monkeypatch):
    """The backend trains on the first half of each batch: half of a pjit
    batch's rows, or the first half of a ring batch's positions (the ring
    executor's microbatch count is fixed when it is built)."""
    from repro.api import backends

    def rows(x):                      # pjit batches: [B, seq]
        return jnp.asarray(x)[: x.shape[0] // 2]

    def positions(x):                 # ring batches: [S, M, mb, seq]
        return jnp.asarray(x)[..., : x.shape[-1] // 2]

    pjit_step, ring_step = backends.PjitBackend.step, backends.FusedBackend.step
    monkeypatch.setattr(backends.PjitBackend, "step", lambda self, b: pjit_step(
        self, {k: rows(v) for k, v in b.items()}))
    monkeypatch.setattr(backends.FusedBackend, "step", lambda self, b: ring_step(
        self, (b[0], positions(b[1]), positions(b[2]))))
    out = _run(toy_cell(name))
    assert not out["correct"] and tripped(out["compared"], sound[name], 10), \
        out["compared"]
