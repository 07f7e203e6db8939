"""Fused RingExecutor vs reference RingTrainer: run in a 4-device subprocess.

Pins the three contracts of the fused end-to-end step (core/executor.py):

  (a) equivalence — losses and exported params match the unfused reference
      over multiple rounds ACROSS a boundary bump (same adamw leaf math,
      different grad plumbing: traced-owner dynamic permutes + in-jit optimizer
      vs static ppermute tables + host optimizer),
  (b) stage-mask correctness — frozen stages' adapters and their Adam moments
      are bit-identical before and after training,
  (c) compile counts — exactly ONE trace/executable per boundary for the fused
      path vs S executables per boundary for the reference.
"""
import json
import os
import subprocess
import sys


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_sub(code: str) -> dict:
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


PRELUDE = """
import json
import jax, jax.numpy as jnp
from repro.core.pipeline import make_ring_mesh
from repro.configs import TrainConfig, get_config
from repro.models import params as P
from repro.core.ring import RingTrainer
from repro.core.executor import RingExecutor

cfg = get_config("stablelm-3b").reduced(n_layers=4, repeats=4,
                                        d_model=128, d_ff=256)
S, M, mb, seq = 4, 3, 1, 32

def fresh_params():
    params = P.materialize(P.param_defs(cfg), jax.random.key(0))
    ad = params["blocks"][0]["adapter"]
    ad["w_up"] = 0.02 * jax.random.normal(jax.random.key(9), ad["w_up"].shape,
                                          jnp.float32).astype(ad["w_up"].dtype)
    return params

mesh = make_ring_mesh(4)
tokens = jax.random.randint(jax.random.key(1), (S, M, mb, seq), 0, cfg.vocab_size)
labels = jax.random.randint(jax.random.key(2), (S, M, mb, seq), 0, cfg.vocab_size)
f32 = lambda x: x.astype(jnp.float32)
maxerr = lambda a, b: max(jax.tree.leaves(jax.tree.map(
    lambda x, y: float(jnp.abs(f32(x) - f32(y)).max()), a, b)))
"""


def test_fused_matches_reference_across_boundary_bump():
    """(a) + (c): 3 rounds crossing boundaries 3 -> 2 -> 1 (interval = S so the
    reference's per-iteration boundary equals the fused per-round boundary)."""
    code = PRELUDE + """
tc = TrainConfig(learning_rate=1e-3, unfreeze_interval=S, n_microbatches=M,
                 batch_size=mb, seq_len=seq)
out = {"ref_loss": [], "fused_loss": [], "ref_b": [], "fused_b": []}
with jax.set_mesh(mesh):
    ref = RingTrainer(cfg, tc, mesh, fresh_params(), S, M)
    ex = RingExecutor(cfg, tc, mesh, fresh_params(), S, M)
    for r in range(3):
        mr = ref.round(tokens, labels)
        me = RingExecutor.materialize_metrics(ex.round(tokens, labels))
        out["ref_loss"].append(mr["loss"])
        out["fused_loss"].append(me["loss"])
        out["ref_b"].append(mr["boundary"])
        out["fused_b"].append(me["boundary"])
    out["param_err"] = maxerr(ref.export_params(), ex.export_params())
    out["fused_traces"] = ex.trace_counts
    out["fused_executables"] = ex.n_executables
    out["ref_executables"] = ref.n_executables
print(json.dumps(out))
"""
    res = _run_sub(code)
    # same schedule on both drivers
    assert res["fused_b"] == [3, 2, 1]
    assert res["ref_b"] == res["fused_b"]
    # (a) losses track within tolerance (bf16 params, different reduce orders)
    for rl, fl in zip(res["ref_loss"], res["fused_loss"]):
        assert abs(rl - fl) < 2e-2, (res["ref_loss"], res["fused_loss"])
    assert res["param_err"] < 5e-2
    # (c) exactly one compilation per boundary, vs S per boundary before
    assert res["fused_executables"] == 3
    assert all(n == 1 for n in res["fused_traces"].values()), res["fused_traces"]
    assert res["ref_executables"] == 3 * 4


def test_frozen_stages_and_moments_untouched():
    """(b): with boundary fixed at 3 (stages 0-2 frozen), frozen stages'
    adapter rows and Adam moments must be BIT-identical after 2 rounds, while
    the hot stage's adapters moved and its moments are nonzero."""
    code = PRELUDE + """
import numpy as np
tc = TrainConfig(learning_rate=1e-3, unfreeze_interval=10**6, n_microbatches=M,
                 batch_size=mb, seq_len=seq)
with jax.set_mesh(mesh):
    ex = RingExecutor(cfg, tc, mesh, fresh_params(), S, M, donate=False)
    ad0 = jax.tree.map(jnp.copy, ex.stage_blocks["adapter"])
    F = ex.boundary_at(0)    # == 3 (initial depth 1, 1 repeat per stage)
    for _ in range(2):
        ex.round(tokens, labels)
    frozen_equal = all(
        bool((a[:F] == b[:F]).all()) for a, b in
        zip(jax.tree.leaves(ad0), jax.tree.leaves(ex.stage_blocks["adapter"])))
    hot_moved = any(
        bool((a[F:] != b[F:]).any()) for a, b in
        zip(jax.tree.leaves(ad0), jax.tree.leaves(ex.stage_blocks["adapter"])))
    m_ad = ex.opt_state["m"]["adapter"]
    frozen_m_zero = all(bool((m[:F] == 0).all()) for m in jax.tree.leaves(m_ad))
    hot_m_nonzero = any(bool((m[F:] != 0).any()) for m in jax.tree.leaves(m_ad))
    print(json.dumps({"F": int(F), "frozen_equal": frozen_equal,
                      "hot_moved": hot_moved, "frozen_m_zero": frozen_m_zero,
                      "hot_m_nonzero": hot_m_nonzero,
                      "traces": ex.trace_counts}))
"""
    res = _run_sub(code)
    assert res["F"] == 3
    assert res["frozen_equal"], "frozen stages' adapters moved"
    assert res["hot_moved"], "hot stage never trained"
    assert res["frozen_m_zero"], "frozen stages' Adam moments were touched"
    assert res["hot_m_nonzero"]
    # same boundary both rounds: still exactly one compilation
    assert res["traces"] == {"3": 1}


def test_seeded_stage_stack_matches_canonical_and_reference_session():
    """``pipeline.init_stage_stack`` (the ``params=None`` path every fused
    session takes) builds bit-for-bit ``stage_stack(materialize(...))``,
    already in ``stage_shardings(mesh)`` with one span per device; a
    default-params fused session starts from exactly the reference session's
    weights (built through the canonical tree) and tracks it over one round
    within the cross-driver pins of the first test."""
    code = PRELUDE + """
import numpy as np
from repro.api import RingSession
from repro.core import pipeline as pl

tc = TrainConfig(learning_rate=1e-3, unfreeze_interval=S, n_microbatches=M,
                 batch_size=mb, seq_len=seq)
key = jax.random.key(tc.seed)
same_bits = lambda a, b: all(
    x.dtype == y.dtype and x.shape == y.shape and x.tobytes() == y.tobytes()
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
with jax.set_mesh(mesh):
    sb, sh = pl.init_stage_stack(cfg, mesh, key)
    want = jax.device_get(pl.stage_stack(
        P.materialize(P.param_defs(cfg), key, cfg.dtype), cfg, S))
    blk_sh, rep_sh = pl.stage_shardings(mesh)
    out = {
        "bit_equal": same_bits(jax.device_get((sb, sh)), want),
        "blocks_placed": all(
            x.sharding.is_equivalent_to(blk_sh, x.ndim)
            and all(s.data.shape[0] == 1 for s in x.addressable_shards)
            for x in jax.tree.leaves(sb)),
        "shared_placed": all(x.sharding.is_equivalent_to(rep_sh, x.ndim)
                             for x in jax.tree.leaves(sh)),
    }
    ses_ref = RingSession.create(cfg, tc, backend="reference", n_stages=S)
    ses_fused = RingSession.create(cfg, tc, backend="fused", n_stages=S)
    out["init_equal"] = same_bits(
        jax.device_get(ses_ref.backend.export_params()),
        jax.device_get(ses_fused.backend.export_params()))
    sr = ses_ref.step((tokens, labels)).materialize()
    sf = ses_fused.step((tokens, labels)).materialize()
    out["losses"] = [sr.loss, sf.loss]
    out["param_err"] = maxerr(ses_ref.backend.export_params(),
                              ses_fused.backend.export_params())
print(json.dumps(out))
"""
    res = _run_sub(code)
    assert res["bit_equal"], "seeded stage stack differs from the canonical one"
    assert res["blocks_placed"] and res["shared_placed"]
    assert res["init_equal"], "fused and reference sessions start apart"
    ref_loss, fused_loss = res["losses"]
    assert abs(ref_loss - fused_loss) < 2e-2, res["losses"]
    assert res["param_err"] < 5e-2
