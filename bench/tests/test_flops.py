"""``bench/flops.py`` counts what the program's forward computes: at a toy
size, its forward count (dense scores, padded head) equals the matrix
products in the jaxpr of ``transformer.forward``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from bench import flops
from bench.cell import load_benchmark, model_config
from bench.correctness import reference_module
from bench.tests.toy import toy_cell
from bench.weights import make_weights

CELLS = [w["name"] for w in load_benchmark()["workloads"]]


def dot_flops(jaxpr, mult=1) -> float:
    total = 0.0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (lc, _), _ = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval.shape
            k = np.prod([lhs[i] for i in lc]) if lc else 1
            total += mult * 2.0 * np.prod(eqn.outvars[0].aval.shape) * k
        sub_mult = mult * eqn.params.get("length", 1) \
            if eqn.primitive.name == "scan" else mult
        for v in eqn.params.values():
            for j in (v if isinstance(v, (tuple, list)) else [v]):
                inner = getattr(j, "jaxpr", j)
                if hasattr(inner, "eqns"):
                    total += dot_flops(inner, sub_mult)
    return total


@pytest.mark.parametrize("name", CELLS)
def test_forward_count_matches_the_programs_jaxpr(name):
    from repro.models import transformer as tfm

    cell = toy_cell(name)
    sz, seq = cell.sizes, cell.traffic["seq_len"]
    ref = reference_module(cell)
    one = SingleDeviceSharding(jax.devices()[0])
    params = make_weights(ref.layout(sz), 1, lambda path: one)
    cfg = model_config(sz, cell.config["registry"])
    tokens = jnp.zeros((2, seq), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda p, t: tfm.forward(p, t, cfg)[0])(
        params, tokens).jaxpr
    want = tokens.size * flops.forward_per_token(
        sz, seq, dense=True, vocab=ref.padded_vocab(sz))
    assert dot_flops(jaxpr) == pytest.approx(want, rel=1e-9)


def test_required_training_count_at_published_width():
    """StableLM-3B at boundary 31, 1024 tokens: 6.207 GFLOP per token."""
    cell = toy_cell(CELLS[0])
    sz = {**cell.sizes, "n_layers": 32, "d_model": 2560, "n_heads": 32,
          "n_kv_heads": 32, "head_dim": 80, "d_ff": 6912,
          "vocab_size": 50304, "adapter_bottleneck": 64, "glu": True,
          "sliding_window": None}
    assert flops.train_per_token(sz, 1024, 31) == pytest.approx(6.2069e9,
                                                                rel=1e-4)
