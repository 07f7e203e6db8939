"""§Perf pair 3 — the paper's own mechanism on the production mesh.

Sweeps the RingAda unfreeze boundary for stablelm-3b x train_4k on the
single-pod mesh and records how the roofline terms + per-chip memory move as
the backward truncates (runs in a subprocess with 512 virtual devices).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import json, sys
import jax
from repro.configs import INPUT_SHAPES, TrainConfig, get_config
from repro.core import training
from repro.launch import inputs as inp
from repro.launch.mesh import make_production_mesh
from repro import roofline as rl

arch = sys.argv[1]
cfg = get_config(arch)
shape = INPUT_SHAPES["train_4k"]
mesh = make_production_mesh()
aspec = inp.act_spec(cfg, shape, mesh)
pspecs = inp.param_specs(cfg, mesh)
aparams = inp.abstract_params(cfg)
batch, bspecs = inp.train_inputs(cfg, shape, mesh)
ospecs = inp.opt_state_specs(cfg, mesh)
ostate = inp.abstract_opt_state(cfg)
tc = TrainConfig()
out = {}
for b in [int(x) for x in sys.argv[2].split(",")]:
    step = training.make_train_step(cfg, tc, b, remat=True, act_spec=aspec,
                                    moe_groups=16)
    with jax.set_mesh(mesh):
        c = jax.jit(step, in_shardings=(pspecs, ospecs, bspecs),
                    out_shardings=(pspecs, ospecs, None),
                    donate_argnums=(0, 1)).lower(aparams, ostate, batch).compile()
    ma = c.memory_analysis()
    cost = c.cost_analysis()
    coll = rl.collective_bytes(c.as_text())
    out[str(b)] = {
        "temp_gib": ma.temp_size_in_bytes / 2**30,
        "hlo_flops_per_chip": cost.get("flops", 0.0),
        "hlo_bytes_per_chip": cost.get("bytes accessed", 0.0),
        "collective_bytes": coll["total"],
    }
print(json.dumps(out))
"""


def run(arch: str = "stablelm-3b", boundaries=(0, 16, 24, 31),
        log=print) -> Dict:
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, "-c", _SCRIPT, arch,
         ",".join(str(b) for b in boundaries)],
        env=env, capture_output=True, text=True, timeout=1800)
    assert res.returncode == 0, res.stderr[-2000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    for b, v in out.items():
        log(f"  boundary={b:>2s} (depth {32 - int(b):2d}): "
            f"temp={v['temp_gib']:.2f}GiB "
            f"bytes/chip={v['hlo_bytes_per_chip']:.2e} "
            f"coll={v['collective_bytes']:.2e}B")
    return out
