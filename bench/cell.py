"""Find a cell's pieces by name: ``BENCHMARK.json``, its configuration file
and its traffic file.

Nothing here is specific to one configuration or one traffic mix: a new cell
is a new entry in ``BENCHMARK.json`` plus, where they are new, a file under
``bench/configs/`` and one under ``bench/traffic/``.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List

BENCH_DIR = Path(__file__).resolve().parent
CHECKOUT = BENCH_DIR.parent

# configuration-file activation names -> the program's names for them
# (``jax.nn.gelu`` defaults to the tanh approximation)
_ACTIVATIONS = {"silu": "silu", "gelu_tanh": "gelu"}


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]          # bench/configs/<config>.json
    traffic: Dict[str, Any]         # bench/traffic/<traffic>.json
    end_to_end: List[Dict[str, Any]]   # metric entries that apply here
    per_layer: List[Dict[str, Any]]

    @property
    def sizes(self) -> Dict[str, Any]:
        return self.config["sizes"]

    @property
    def tokens_per_call(self) -> int:
        """Training tokens one ``RingSession.step`` consumes."""
        t = self.traffic
        if t["backend"] == "pjit":
            return t["batch_size"] * t["seq_len"]
        return (t["n_stages"] * t["n_microbatches"] * t["batch_size"]
                * t["seq_len"])


def load_benchmark(root: Path = CHECKOUT) -> Dict[str, Any]:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _applies(metric: Dict[str, Any], cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") is None or metric["moves"] in reported


def load_cell(name: str, root: Path = CHECKOUT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(root / configs[w["config"]]["file"]) as f:
        config = json.load(f)
    with open(BENCH_DIR / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _applies(m, name, reported)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer)


def model_config(sizes: Dict[str, Any], registry: str):
    """The program's ``ModelConfig`` for a configuration file's sizes: the
    registry entry with every size of the file written over it."""
    import dataclasses

    from repro.configs import get_config

    base = get_config(registry)
    return dataclasses.replace(
        base,
        n_layers=sizes["n_layers"], repeats=sizes["n_layers"],
        pattern=(("dense", 1),),
        d_model=sizes["d_model"], n_heads=sizes["n_heads"],
        n_kv_heads=sizes["n_kv_heads"], head_dim=sizes["head_dim"],
        d_ff=sizes["d_ff"], vocab_size=sizes["vocab_size"],
        vocab_pad_to=sizes["vocab_pad_to"], glu=sizes["glu"],
        activation=_ACTIVATIONS[sizes["activation"]],
        rope=True, rope_theta=sizes["rope_theta"],
        sliding_window=sizes["sliding_window"], norm=sizes["norm"],
        qkv_bias=False, tie_embeddings=False, head_out=None,
        adapter=dataclasses.replace(
            base.adapter, bottleneck=sizes["adapter_bottleneck"],
            activation=_ACTIVATIONS[sizes["adapter_activation"]],
            zero_init_up=True),
        dtype=sizes["dtype"])


def train_config(traffic: Dict[str, Any]):
    """The program's ``TrainConfig`` for a traffic file: the depth is held
    for the whole run (the interval never elapses)."""
    from repro.configs import TrainConfig

    opt = traffic["optimizer"]
    return TrainConfig(
        learning_rate=opt["learning_rate"], weight_decay=opt["weight_decay"],
        beta1=opt["beta1"], beta2=opt["beta2"], eps=opt["eps"],
        warmup_steps=opt["warmup_steps"],
        batch_size=traffic["batch_size"], seq_len=traffic["seq_len"],
        n_microbatches=traffic["n_microbatches"],
        n_stages=traffic["n_stages"],
        initial_unfreeze_depth=traffic["depth"],
        unfreeze_interval=10 ** 9)
