"""Each cell of ``BENCHMARK.json`` at a size the CPU runs in seconds: the
same configuration keys and traffic keys, smaller numbers."""
from __future__ import annotations

import dataclasses

from bench.cell import Cell, load_cell

TOY_SIZES = {"n_layers": 4, "d_model": 64, "n_heads": 4, "head_dim": 16,
             "d_ff": 128, "vocab_size": 250, "adapter_bottleneck": 8}
TOY_TRAFFIC = {"pjit": {"batch_size": 4, "seq_len": 32},
               "fused": {"batch_size": 1, "seq_len": 32}}


def toy_cell(name: str) -> Cell:
    cell = load_cell(name)
    sizes = {**cell.sizes, **TOY_SIZES}
    sizes["n_kv_heads"] = min(cell.sizes["n_kv_heads"], sizes["n_heads"])
    if sizes["sliding_window"]:
        sizes["sliding_window"] = 16
    traffic = {**cell.traffic, **TOY_TRAFFIC[cell.traffic["backend"]]}
    if traffic["n_stages"] > 1:
        traffic["n_microbatches"] = 2
    return dataclasses.replace(cell, config={**cell.config, "sizes": sizes},
                               traffic=traffic)
