"""Compiles for a TPU v5e that is described, not attached.

The TPU compiler is installed with jaxlib, so the main path's programs can be
compiled at ``stablelm-3b``'s published width (d_model 2560, 32 heads,
d_ff 6912, vocab 50304) for a ``v5e:2x2`` topology without the chip.  The
compiler refuses what the chip would refuse: a Pallas kernel that cannot
tile, a program that does not fit HBM.  Nothing runs, so nothing here says
anything about results or times.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.  Keep every test that needs the topology in this one file.
"""
import dataclasses
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, SingleDeviceSharding

from repro.configs import TrainConfig, get_config
from repro.core import pipeline as pl
from repro.core import training
from repro.core.executor import make_fused_round, ring_opt_init, ring_opt_specs
from repro.kernels import adapter_fused as af
from repro.kernels import flash_attention as fa
from repro.models import params as prm
from repro.optim import adamw

HBM_V5E = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / topology support here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _cut(n_blocks: int):
    """stablelm-3b at published width, depth cut to ``n_blocks``."""
    cfg = get_config("stablelm-3b")
    return dataclasses.replace(cfg, n_layers=n_blocks, repeats=n_blocks)


@pytest.mark.parametrize("kernel", ["adapter_fused", "flash_attention"])
def test_kernel_compiles_for_v5e(kernel, one_chip):
    """The Pallas kernels lower to Mosaic (not interpret mode) at this
    model's widths: the adapter over 4096 tokens of d_model 2560 with the
    bottleneck of 64, attention over 32 heads of 80 at seq 2048."""
    bf16 = jnp.bfloat16
    if kernel == "adapter_fused":
        fn = lambda h, wd, wu: af.adapter_fused(h, wd, wu, interpret=False)
        shapes = [(4096, 2560), (2560, 64), (64, 2560)]
    else:
        fn = lambda q, k, v: fa.flash_attention(q, k, v, interpret=False)
        shapes = [(1, 32, 2048, 80)] * 3
    args = [jax.ShapeDtypeStruct(s, bf16, sharding=one_chip) for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_ring_round_compiles_for_v5e_2x2(topo):
    """One fused ring round for the four-chip mesh — the step the four-chip
    smoke phase runs, at 4 blocks (one per chip) instead of 32: M=4
    microbatches of 1024 tokens, all adapters hot.  Each chip holds only
    its own stage's blocks, and the round's ring hops are collectives."""
    S, M, seq = 4, 4, 1024
    cfg = _cut(S)
    mesh = Mesh(np.array(topo.devices), ("stage",),
                axis_types=(AxisType.Auto,))
    stage, rep = pl.stage_shardings(mesh)
    key = jax.eval_shape(lambda: jax.random.key(0))
    blocks, shared = jax.eval_shape(
        lambda k: pl.stage_stack(prm.materialize(prm.param_defs(cfg), k,
                                                 cfg.dtype), cfg, S), key)
    opt = jax.eval_shape(ring_opt_init, blocks, shared)
    opt = jax.tree.map(lambda s, sub: _sds(sub, NamedSharding(mesh, s)),
                       ring_opt_specs(), opt)
    tokens = jax.ShapeDtypeStruct((S, M, 1, seq), jnp.int32, sharding=stage)
    tc = TrainConfig(n_microbatches=M, batch_size=1, seq_len=seq)
    fn = make_fused_round(cfg, tc, mesh, n_stages=S, boundary=0, n_micro=M)
    args = (_sds(blocks, stage), _sds(shared, rep), opt, tokens, tokens)
    compiled = jax.jit(fn, donate_argnums=(0, 1, 2)).lower(*args).compile()

    mem = compiled.memory_analysis()
    # a chip holds its shard of each argument: its own stage's quarter of
    # the blocks and adapter moments, a whole copy of the replicated leaves
    # (the compiler pads small buffers to its tiles: 1% slack)
    per_chip = sum(math.prod(x.sharding.shard_shape(x.shape))
                   * x.dtype.itemsize for x in jax.tree.leaves(args))
    assert mem.argument_size_in_bytes < 1.01 * per_chip, (
        mem.argument_size_in_bytes, per_chip)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_V5E
    assert "collective-permute" in compiled.as_text()


def test_pjit_trunk_attention_is_the_flash_kernel(one_chip):
    """Lowered for the chip, the pjit step at boundary 1 of 2 runs the
    frozen block's attention as the named Pallas kernel, inside the trunk's
    scope and under the attention tag (at 2 x 1024 tokens)."""
    cfg = _cut(2)
    key = jax.eval_shape(lambda: jax.random.key(0))
    params = jax.eval_shape(
        lambda k: prm.materialize(prm.param_defs(cfg), k, cfg.dtype), key)
    opt = jax.eval_shape(lambda p: adamw.init(training.full_trainable(p)),
                         params)
    batch = {k: jax.ShapeDtypeStruct((2, 1024), jnp.int32, sharding=one_chip)
             for k in ("tokens", "labels")}
    step = jax.jit(training.make_train_step(cfg, TrainConfig(), 1))
    text = step.lower(_sds(params, one_chip), _sds(opt, one_chip),
                      batch).compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert calls and all(fa.NAME in ln for ln in calls), calls
    for ln in calls:
        assert "ringada.trunk" in ln and "ringada.attention" in ln, ln


def test_fused_round_one_chip_has_no_kernel(topo):
    """The fused ring at S = 1, all blocks hot: every attention it runs is
    differentiated, so its round holds no Pallas call."""
    cfg = _cut(2)
    mesh = Mesh(np.array(topo.devices[:1]), ("stage",),
                axis_types=(AxisType.Auto,))
    stage, rep = pl.stage_shardings(mesh)
    key = jax.eval_shape(lambda: jax.random.key(0))
    blocks, shared = jax.eval_shape(
        lambda k: pl.stage_stack(prm.materialize(prm.param_defs(cfg), k,
                                                 cfg.dtype), cfg, 1), key)
    opt = jax.eval_shape(ring_opt_init, blocks, shared)
    opt = jax.tree.map(lambda s, sub: _sds(sub, NamedSharding(mesh, s)),
                       ring_opt_specs(), opt)
    tokens = jax.ShapeDtypeStruct((1, 1, 1, 1024), jnp.int32, sharding=stage)
    tc = TrainConfig(n_microbatches=1, batch_size=1, seq_len=1024)
    fn = make_fused_round(cfg, tc, mesh, n_stages=1, boundary=0, n_micro=1)
    args = (_sds(blocks, stage), _sds(shared, rep), opt, tokens, tokens)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" not in text


def test_activation_memory_shrinks_with_boundary(one_chip):
    """The paper's memory claim: the frozen trunk stores no residuals.

    ``training.make_train_step`` compiled for one v5e chip at published
    width, 4 blocks, on the pjit smoke phase's batch of 8 x 1024 tokens.
    Blocks below the boundary run under stop_gradient, so the step's
    temporaries fall with every block the boundary freezes; with 3 of 4
    blocks frozen only the top block's residuals remain, about a quarter of
    the all-hot step's temporaries (0.25 in the compile; the bound 0.4
    leaves room for the step's fixed part: head, logit chunks, optimizer).
    At 2 x 1024 tokens the compiler's temporaries are not monotone in the
    boundary (b=1 above b=0), an open defect listed in ROADMAP.md."""
    cfg = _cut(4)
    tc = TrainConfig()
    key = jax.eval_shape(lambda: jax.random.key(0))
    params = jax.eval_shape(
        lambda k: prm.materialize(prm.param_defs(cfg), k, cfg.dtype), key)
    opt = jax.eval_shape(lambda p: adamw.init(training.full_trainable(p)),
                         params)
    batch = {k: jax.ShapeDtypeStruct((8, 1024), jnp.int32, sharding=one_chip)
             for k in ("tokens", "labels")}
    temps = []
    for b in range(4):
        step = jax.jit(training.make_train_step(cfg, tc, b))
        compiled = step.lower(_sds(params, one_chip), _sds(opt, one_chip),
                              batch).compile()
        temps.append(compiled.memory_analysis().temp_size_in_bytes)
    assert temps[0] > temps[1] > temps[2] > temps[3], temps
    assert temps[3] < 0.4 * temps[0], temps
