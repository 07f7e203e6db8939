"""Dry-run machinery on a small virtual mesh (subprocess, 16 host devices).

Validates the same lower->compile->analyze pipeline the 512-chip dry-run uses,
at a size CI can afford, plus the input-spec builders and the analytic-FLOPs
cross-check on real configs.
"""
import json
import os
import subprocess
import sys

import pytest

from repro import roofline as rl
from repro.configs import ASSIGNED, INPUT_SHAPES, get_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_lower_compile_small_mesh():
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import json, dataclasses
import jax
from jax.sharding import AxisType
from repro.configs import TrainConfig, get_config
from repro.core import training
from repro.launch import inputs as inp
from repro import sharding as sh
from repro.models import params as prm

mesh = jax.make_mesh((4, 4), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
out = {}
for arch in ["stablelm-3b", "olmoe-1b-7b", "rwkv6-7b"]:
    cfg = get_config(arch).reduced(d_model=256, n_heads=4, n_kv_heads=4)
    rules = sh.default_rules(mesh)
    defs = prm.param_defs(cfg)
    pspecs = prm.specs(defs, rules)
    aparams = prm.abstract(defs, cfg.dtype)
    import jax.numpy as jnp
    batch = {"tokens": jax.ShapeDtypeStruct((16, 64), jnp.int32),
             "labels": jax.ShapeDtypeStruct((16, 64), jnp.int32)}
    from jax.sharding import PartitionSpec as P
    bspecs = {"tokens": P("data"), "labels": P("data")}
    step = training.make_train_step(cfg, TrainConfig(), 1, remat=True)
    ostate = inp.abstract_opt_state(cfg)
    with jax.set_mesh(mesh):
        c = jax.jit(step).lower(aparams, ostate, batch).compile()
    ma = c.memory_analysis()
    out[arch] = {"temp": ma.temp_size_in_bytes,
                 "flops": c.cost_analysis().get("flops", 0.0)}
print(json.dumps(out))
"""
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    for arch, v in out.items():
        assert v["temp"] > 0 and v["flops"] > 0


def test_analytic_flops_scaling():
    """Analytic FLOPs must scale linearly in tokens and superlinearly never."""
    cfg = get_config("stablelm-3b")
    t = rl.analytic_flops(cfg, INPUT_SHAPES["train_4k"])
    p = rl.analytic_flops(cfg, INPUT_SHAPES["prefill_32k"])
    d = rl.analytic_flops(cfg, INPUT_SHAPES["decode_32k"])
    # train has bwd (~3x fwd-per-token) and 8x fewer ctx tokens than prefill
    assert t > 0 and p > 0 and d > 0
    assert d < t and d < p
    # decode flops per token ~= prefill flops per token at same ctx order
    per_tok_p = p / (32 * 32768)
    per_tok_d = d / 128
    assert 0.3 < per_tok_d / per_tok_p < 3.5


def test_analytic_close_to_model_flops():
    """Analytic >= 2*N*D (it adds the quadratic attention term, which at 32k
    context legitimately rivals the weight FLOPs) but within ~3x."""
    for name in ["stablelm-3b", "qwen2.5-3b"]:
        cfg = get_config(name)
        shape = INPUT_SHAPES["prefill_32k"]
        ana = rl.analytic_flops(cfg, shape)
        mf = rl.model_flops(cfg, shape)["model_flops"]
        assert 0.3 < mf / ana < 1.1, (name, mf / ana)


@pytest.mark.parametrize("name", ASSIGNED)
def test_every_arch_has_analytic_flops(name):
    cfg = get_config(name)
    for shape in INPUT_SHAPES.values():
        from repro.configs import shape_runnable
        if not shape_runnable(cfg, shape)[0]:
            continue
        assert rl.analytic_flops(cfg, shape) > 0
