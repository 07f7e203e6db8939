"""Readings that the limits of a cell are set from, at the cell's own size.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,...,12 \\
        --variant-seeds 3 --out chiprun_out/calibrate-<cell>.json

For every seed, in one process: the program's first three calls (the run's
own set-up, no window), then the reference; their numbers are the *sound*
readings.  On the first ``--variant-seeds`` seeds, the reference is also put
in the program's place as

  * ``control``: the reference in float8 (e4m3), the precision below bf16;
  * ``unchanged``: every step returns its state unchanged;
  * ``half_batch``: the loss, and so the gradient, over half of each batch;
  * ``no_exchange`` (rings of several chips): the hot layers get the
    embeddings, as if the frozen trunk's hops were left out;

and compared with the true reference.  ``bench/limits/`` holds
the limits set from these readings; ``PERF.md`` gives both.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1])]

from bench import correctness as cx  # noqa: E402
from bench.cell import load_cell  # noqa: E402
from bench.run import log, set_up, use_compile_cache  # noqa: E402


def variants(cell):
    out = [("control", dict(control=True)),
           ("unchanged", dict(fault="unchanged")),
           ("half_batch", dict(fault="half_batch"))]
    if cell.traffic["backend"] != "pjit" and cell.traffic["n_stages"] > 1:
        out.append(("no_exchange", dict(fault="no_exchange")))
    return out


def calibrate(cell, seeds, n_variant_seeds, devices):
    rows = []
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        sess, probe, feed = set_up(cell, seed, devices)
        del sess
        gc.collect()
        t1 = time.perf_counter()
        ref = cx.reference_readings(cell, seed, feed.history, devices)
        t2 = time.perf_counter()
        row = {"seed": seed, "sound": cx.numbers(probe.readings(), ref),
               "program_losses": probe.losses, "reference_losses":
               ref["losses"], "program_s": t1 - t0, "reference_s": t2 - t1}
        if i < n_variant_seeds:
            for name, kw in variants(cell):
                var = cx.reference_readings(cell, seed, feed.history, devices,
                                            **kw)
                row[name] = cx.numbers({**var, "frozen_changed": 0}, ref)
        log(json.dumps(row))
        rows.append(row)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--variant-seeds", type=int, default=3)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    use_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        log(f"calibrate: {cell.name} needs {cell.chips} TPU chips")
        return 2
    seeds = [int(s) for s in args.seeds.split(",")]
    rows = calibrate(cell, seeds, args.variant_seeds, devices[:cell.chips])
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"workload": cell.name, "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
