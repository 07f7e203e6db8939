"""The benchmark: one cell of ``BENCHMARK.json``, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up makes the weights on the device from ``--seed``, builds the session
through ``RingSession.create`` on the cell's backend, and drives its first
three calls through the window's own call and feed: they compile, warm up
and give the program's readings for the correctness check.  The window then
runs ``RingSession.step()`` and ``.materialize()`` back to back (a closed
loop, one host sync per call) for ``--seconds``.  A compile inside the
window fails the run.  After the window the program's state is freed and
the plain reference replays the three calls from the seed.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``compared``: each number of the correctness check
beside its limit.  Without a TPU, or with fewer chips than the cell asks
for, it prints no result and exits non-zero.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

T_START = time.perf_counter()

CHECKOUT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]

from bench.cell import BENCH_DIR, Cell, load_cell  # noqa: E402

CACHE_DIR = CHECKOUT / ".jax_cache"
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def use_compile_cache() -> str:
    """JAX's persistent cache where ``JAX_COMPILATION_CACHE_DIR`` says, else
    at the fixed ``<checkout>/.jax_cache``; every program is kept."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileClock:
    """Counts and times JAX's tracing, lowering and compiling (copied from
    the program's ``chip_smoke.py``)."""

    def __init__(self):
        import jax

        self.secs, self.events = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event in COMPILE_EVENTS:
            self.secs += secs
            self.events += 1


def load_metric(name: str):
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def executable_temp_bytes(sess, batch) -> int:
    """Compiler temporaries of the executable the window runs, per chip."""
    be = sess.backend
    if be.kind == "pjit":
        (fn,) = be._fns.values()
        args = (be._params, be._opt, batch)
    else:
        d = be.driver
        (fn,) = d._fns.values()
        _, tok, lab = batch
        args = (d.stage_blocks, d.shared, d.opt_state, tok, lab)
    return int(fn.lower(*args).compile().memory_analysis().temp_size_in_bytes)


def set_up(cell: Cell, seed: int, devices):
    """Weights from the seed, the session on the cell's backend, and its
    first three calls through the window's own call and feed.  Returns
    (session, probe, feed)."""
    from bench import correctness as cx
    from bench.cell import model_config, train_config
    from bench.datagen import Feed
    from bench.weights import make_weights
    from repro.api import RingSession

    sz, t = cell.sizes, cell.traffic
    ring = t["backend"] != "pjit"
    weights = make_weights(cx.reference_module(cell).layout(sz), seed,
                           cx.placement(cell, devices))
    probe = cx.ProgramProbe(cell, weights, devices[0])
    feed = Feed(t, sz["vocab_size"], seed)
    sess = RingSession.create(model_config(sz, cell.config["registry"]),
                              train_config(t), backend=t["backend"],
                              n_stages=t["n_stages"] if ring else None,
                              params=weights, data=feed, log=log)
    del weights
    for call in range(cx.CALLS):
        probe.record(sess.step().materialize())
        if call == 0:
            probe.after_first(sess)
    probe.after_third(sess)
    return sess, probe, feed


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices, *,
             limits, keep_trace: str = None, t_start: float = T_START):
    """Set-up, window and check of one run; returns the result object."""
    import jax

    from bench import correctness as cx
    from bench import flops

    clock = CompileClock()
    ring = cell.traffic["backend"] != "pjit"
    sess, probe, feed = set_up(cell, seed, devices)
    boundary = cx.boundary_of(cell)
    temp = executable_temp_bytes(sess, feed.history[0])
    in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0)
              for d in devices]          # the CPU reports none
    hbm = max(in_use) + temp
    log(f"set-up: bytes in use per chip {in_use}, executable temporaries "
        f"{temp} per chip; compile seconds {clock.secs:.3f}")
    counts0 = (sess.backend.driver.compile_counts() if ring
               else sess.backend.compile_count)
    events0 = clock.events

    trace_dir = None
    if trace:
        trace_dir = keep_trace or tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(trace_dir)
    setup_s = time.perf_counter() - t_start
    rounds, losses = [], []
    w0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.step"):
                m = sess.step()
            t1 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.materialize"):
                m = m.materialize()
            t2 = time.perf_counter()
            rounds.append({"t0": t0, "t1": t1, "t2": t2,
                           "tokens": cell.tokens_per_call})
            losses.append(m.loss)
            if t2 - w0 >= seconds:
                break
    window_s = rounds[-1]["t2"] - w0
    if trace:
        jax.profiler.stop_trace()
    compiles = clock.events - events0
    counts1 = (sess.backend.driver.compile_counts() if ring
               else sess.backend.compile_count)
    failed = sum(1 for x in losses if not math.isfinite(x))

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": hbm}
    del sess, m
    gc.collect()

    rec = SimpleNamespace(rounds=rounds, window_s=window_s, setup_s=setup_s,
                          hbm_bytes=hbm, chips=len(devices),
                          flops_per_call=flops.per_call(cell, boundary),
                          peak_flops=None, trace=None)
    breakdown = None
    if trace:
        from bench.peaks import PEAKS
        from bench.trace import Trace

        # main() refuses a device without peaks; a CPU rehearsal has none
        rec.peak_flops = PEAKS.get(devices[0].device_kind, {}).get("bf16_flops")
        path = next(Path(trace_dir).rglob("*.xplane.pb"))
        rec.trace = Trace(str(path))
        busy = rec.trace.busy_s()
        log(f"trace: window {rec.trace.window_s()} s, busy per chip {busy}")
        if busy:
            device["busy_s"] = sum(busy.values()) / len(busy)
            device["window_s"] = rec.trace.window_s()
            breakdown = rec.trace.breakdown()
        if not keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)

    entries = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for e in entries:
        val = load_metric(e["name"])(rec)
        if val is not None:
            metrics[e["name"]] = {"value": val, "unit": e["unit"]}

    # the reference replays the first three calls, after the window
    t_ref = time.perf_counter()
    ref_read = cx.reference_readings(cell, seed, feed.history, devices)
    nums = cx.numbers(probe.readings(), ref_read)
    nums["window_compiles"] = float(compiles + (counts1 != counts0))
    ok, shown = cx.verdict(nums, limits)
    shown["window_compiles"] = {"value": nums["window_compiles"], "limit": 0}
    shown["failed_calls"] = {"value": failed, "limit": 0}
    ok = ok and nums["window_compiles"] == 0 and failed == 0
    log(f"program losses {probe.losses}")
    log(f"reference losses {ref_read['losses']}")
    ms = sorted((1e3 * (r["t2"] - r["t0"]), 1e3 * (r["t1"] - r["t0"]), i)
                for i, r in enumerate(rounds))
    log(f"window: {len(rounds)} calls in {window_s} s; call ms min "
        f"{ms[0][0]:.2f} median {ms[len(ms) // 2][0]:.2f}; slowest (ms, "
        f"of which in step(), index) {[tuple(round(x, 2) for x in m) for m in ms[-3:]]}; "
        f"reference {time.perf_counter() - t_ref:.1f} s")
    for name, v in shown.items():
        log(f"compared {name} {v['value']!r} limit {v['limit']!r}")

    out = {"correct": bool(ok), "attempted": len(rounds), "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = shown
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    from bench.correctness import load_limits

    limits = load_limits(cell)
    cache = use_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"bench: needs a TPU, JAX found {devices[0].platform!r}")
        return 2
    if len(devices) < cell.chips:
        log(f"bench: {cell.name} needs {cell.chips} chips, JAX found "
            f"{len(devices)}")
        return 2
    from bench.peaks import peaks

    peaks(devices[0].device_kind)
    log(f"bench: {cell.name} on {cell.chips} x {devices[0].device_kind}, "
        f"seed {args.seed}, compile cache {cache}")
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   devices[:cell.chips], limits=limits)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
