"""Ring pipeline (shard_map + ppermute): run in a 4-device subprocess.

shard_map needs real (host) devices; the main pytest process keeps the default
1-device backend, so these tests re-exec themselves with
XLA_FLAGS=--xla_force_host_platform_device_count=4.
"""
import json
import os
import subprocess
import sys

import pytest

from repro.core.pipeline import pipeline_tick_counts

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
from repro.configs import get_config
from repro.models import params as P, transformer as T
from repro.core import pipeline as pl, training
from repro.models.losses import cross_entropy

cfg = get_config("stablelm-3b").reduced(n_layers=4, repeats=4)
params = P.materialize(P.param_defs(cfg), jax.random.key(0))
ad = params["blocks"][0]["adapter"]
ad["w_up"] = 0.02 * jax.random.normal(jax.random.key(9), ad["w_up"].shape,
                                      jnp.float32).astype(ad["w_up"].dtype)
mesh = make_ring_mesh(4)
S, M, mb, seq = 4, 3, 2, 32
tokens = jax.random.randint(jax.random.key(1), (S, M, mb, seq), 0, cfg.vocab_size)
labels = jax.random.randint(jax.random.key(2), (S, M, mb, seq), 0, cfg.vocab_size)
stage_blocks, shared = pl.stage_stack(params, cfg, S)
"""


@pytest.mark.slow
def test_ring_loss_matches_reference_all_owners():
    code = PRELUDE + """
res = {}
with jax.set_mesh(mesh):
    for owner in range(4):
        fn = jax.jit(pl.make_ring_round(cfg, mesh, n_stages=S, owner=owner,
                                        boundary=0, n_micro=M))
        loss = fn(stage_blocks, shared, tokens, labels)
        ts = tokens[owner].reshape(M * mb, seq)
        ls = labels[owner].reshape(M * mb, seq)
        logits, _ = T.forward(params, ts, cfg)
        ref, _ = cross_entropy(logits, ls)
        res[str(owner)] = [float(loss), float(ref)]
print(json.dumps(res))
"""
    res = _run_sub(code)
    for owner, (got, want) in res.items():
        assert abs(got - want) < 3e-3, (owner, got, want)


@pytest.mark.slow
def test_ring_grads_match_pjit_path():
    code = PRELUDE + """
owner, boundary = 1, 2
with jax.set_mesh(mesh):
    fn = jax.jit(pl.make_ring_train_round(cfg, mesh, n_stages=S, owner=owner,
                                          boundary=boundary, n_micro=M))
    loss, (gad, ghead) = fn(stage_blocks, shared, tokens, labels)
ts = tokens[owner].reshape(M * mb, seq)
ls = labels[owner].reshape(M * mb, seq)
def loss_fn(tr):
    logits, _ = T.forward(params, ts, cfg, boundary=boundary,
                          hot_adapters=tr["adapters"], head_params=tr["head"])
    return cross_entropy(logits, ls)[0]
tr = training.split_trainable(params, boundary)
ref = jax.grad(loss_fn)(tr)
ra = ref["adapters"][0]["w_up"]
ga = gad["w_up"].reshape(4, *gad["w_up"].shape[2:])[boundary:]
err_ad = float(jnp.abs(ra.reshape(ga.shape).astype(jnp.float32)
                       - ga.astype(jnp.float32)).max())
err_hd = float(jnp.abs(ref["head"]["w"].astype(jnp.float32)
                       - ghead["w"].astype(jnp.float32)).max())
frozen_zero = bool((gad["w_up"][:boundary] == 0).all())
print(json.dumps({"err_ad": err_ad, "err_hd": err_hd,
                  "frozen_zero": frozen_zero}))
"""
    res = _run_sub(code)
    assert res["err_ad"] < 5e-3
    assert res["err_hd"] < 5e-3
    assert res["frozen_zero"]


@pytest.mark.slow
def test_ring_trainer_rounds_reduce_loss():
    code = PRELUDE + """
from repro.configs import TrainConfig
from repro.core.ring import RingTrainer
from repro.data.pipeline import make_client_datasets, RingBatcher
tc = TrainConfig(learning_rate=3e-3, unfreeze_interval=4, n_microbatches=M,
                 batch_size=mb, seq_len=seq)
trainer = RingTrainer(cfg, tc, mesh, params, S, M)
clients = make_client_datasets(S, vocab=cfg.vocab_size, n_per_client=32,
                               seq=seq, seed=0)
rb = RingBatcher(clients, M, mb, seed=0)
losses = []
with jax.set_mesh(mesh):
    for r in range(6):
        tk, lb = rb.next()
        m = trainer.round(tk, lb)
        losses.append(m["loss"])
print(json.dumps({"losses": losses}))
"""
    res = _run_sub(code)
    assert res["losses"][-1] < res["losses"][0]


def test_ring_round_local_matches_static_owner_round():
    """The traced-owner round (and therefore the phase_a/phase_b split it is
    composed from — the same halves the fused executor runs) reproduces the
    static-owner reference ``make_ring_round`` for every owner."""
    code = PRELUDE + """
from jax.sharding import PartitionSpec as Pspec
boundary = 2
local = pl.ring_round_local(cfg, n_stages=S, boundary=boundary, n_micro=M)

def global_local_round(owner, stage_blocks, shared, tokens, labels):
    def body(owner, stage_blocks, shared, tokens, labels):
        my_blocks = jax.tree.map(lambda x: x[0], stage_blocks)
        my_tokens = tokens[0]
        seq_ = my_tokens.shape[2]
        mb_ = my_tokens.shape[1]
        pos = jnp.broadcast_to(jnp.arange(seq_, dtype=jnp.int32)[None],
                               (mb_, seq_))
        shared_rest = {k: v for k, v in shared.items() if k != "head"}
        emb_g = pl.gather_embeddings(cfg, shared_rest, my_tokens, pos)
        l_loc = local(owner, my_blocks, shared, emb_g, labels[0])
        return jax.lax.psum(l_loc, "stage")
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(Pspec(), Pspec("stage"), Pspec(), Pspec("stage"),
                  Pspec("stage")),
        out_specs=Pspec())(owner, stage_blocks, shared, tokens, labels)

res = {}
with jax.set_mesh(mesh):
    fused = jax.jit(global_local_round)
    for owner in range(4):
        ref_fn = jax.jit(pl.make_ring_round(cfg, mesh, n_stages=S, owner=owner,
                                            boundary=boundary, n_micro=M))
        ref = ref_fn(stage_blocks, shared, tokens, labels)
        got = fused(jnp.int32(owner), stage_blocks, shared, tokens, labels)
        res[str(owner)] = [float(got), float(ref)]
print(json.dumps(res))
"""
    res = _run_sub(code)
    for owner, (got, want) in res.items():
        assert abs(got - want) < 1e-4, (owner, got, want)


def test_tick_counts():
    # PipeAdapter: fwd/bwd both M+S-1; RingAda shrinks bwd by frozen stages
    t0 = pipeline_tick_counts(4, 8, boundary=0, lps=1)
    assert t0["bwd_ticks"] == 11
    t2 = pipeline_tick_counts(4, 8, boundary=2, lps=1)
    assert t2["bwd_ticks"] == 9
    assert t2["frozen_stages"] == 2
    t3 = pipeline_tick_counts(4, 8, boundary=3, lps=1)
    assert t3["bwd_ticks"] == 8
    # actcache steady state: Phase A's M+F-1 ticks vanish, backward unchanged
    t2c = pipeline_tick_counts(4, 8, boundary=2, lps=1, cached=True)
    assert t2c["fwd_ticks"] == t2["fwd_ticks"] - (8 + 2 - 1)
    assert t2c["bwd_ticks"] == t2["bwd_ticks"]
    assert pipeline_tick_counts(4, 8, boundary=0, lps=1, cached=True) == {
        **t0, "fwd_ticks": t0["fwd_ticks"]}
