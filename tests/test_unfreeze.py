"""RingAda's core mechanism: scheduled unfreezing + truncated backprop."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core import training
from repro.core.unfreeze import (UnfreezeSchedule, boundary_schedule,
                                 depth_to_boundary)
from repro.models import params as prm
from repro.models import transformer as tfm


def test_schedule_matches_algorithm1():
    # paper: start d=1 (head + top adapter), every k=40 steps d += 1
    s = UnfreezeSchedule(initial_depth=1, interval=40)
    assert s.depth_at(0, 12) == 1
    assert s.depth_at(39, 12) == 1
    assert s.depth_at(40, 12) == 2
    assert s.depth_at(400, 12) == 11
    assert s.depth_at(4000, 12) == 12       # capped at n_layers


def test_schedule_explicit_depths():
    s = UnfreezeSchedule(interval=10, depths=(1, 2, 5))
    assert s.depth_at(0, 12) == 1
    assert s.depth_at(19, 12) == 2
    assert s.depth_at(25, 12) == 5
    assert s.depth_at(9999, 12) == 5          # last entry holds forever
    assert s.depth_at(25, 3) == 3             # capped at n_blocks


def test_schedule_rejects_non_monotone():
    """The activation cache's invalidation contract: boundary never increases,
    i.e. depth never shrinks. Anything else must fail loudly at construction."""
    with pytest.raises(ValueError, match="non-monotone"):
        UnfreezeSchedule(interval=10, depths=(1, 3, 2))
    with pytest.raises(ValueError, match="interval"):
        UnfreezeSchedule(interval=0)
    with pytest.raises(ValueError, match="initial_unfreeze_depth"):
        UnfreezeSchedule(initial_depth=0)
    with pytest.raises(ValueError, match="depths"):
        UnfreezeSchedule(depths=())


def test_boundary_schedule_rejects_rising_boundary():
    """Defense-in-depth: even a custom depth_at that shrinks depth mid-run is
    caught when the segments are materialized."""
    class Bad(UnfreezeSchedule):
        def depth_at(self, step, n_blocks):
            return 3 if step < 5 else 1        # depth shrinks: boundary rises

    cfg = get_config("mbert-squad").reduced(n_layers=4, repeats=4)
    with pytest.raises(ValueError, match="non-monotone"):
        boundary_schedule(cfg, Bad(), 20)


def test_depth_to_boundary_uniform():
    cfg = get_config("stablelm-3b")
    assert depth_to_boundary(cfg, 1) == 31
    assert depth_to_boundary(cfg, 32) == 0


def test_depth_to_boundary_patterned():
    cfg = get_config("llama-3.2-vision-11b")   # 5 layers per repeat, 8 repeats
    assert depth_to_boundary(cfg, 1) == 7       # rounds up to one superblock
    assert depth_to_boundary(cfg, 5) == 7
    assert depth_to_boundary(cfg, 6) == 6
    assert depth_to_boundary(cfg, 40) == 0


def test_boundary_schedule_segments():
    cfg = get_config("mbert-squad").reduced(n_layers=4, repeats=4)
    segs = boundary_schedule(cfg, UnfreezeSchedule(1, 10), 40)
    assert segs[0] == (0, 10, 3)
    assert segs[1] == (10, 20, 2)
    assert segs[-1][2] == 0
    # segments tile [0, total) exactly
    assert segs[0][0] == 0 and segs[-1][1] == 40
    for (a, b, _), (c, d, _) in zip(segs, segs[1:]):
        assert b == c


def _setup(n_layers=6):
    cfg = get_config("stablelm-3b").reduced(n_layers=n_layers, repeats=n_layers)
    params = prm.materialize(prm.param_defs(cfg), jax.random.key(0), cfg.dtype)
    batch = {
        "tokens": jax.random.randint(jax.random.key(1), (2, 64), 0,
                                     cfg.vocab_size),
        "labels": jax.random.randint(jax.random.key(2), (2, 64), 0,
                                     cfg.vocab_size),
    }
    return cfg, params, batch


def test_forward_invariant_to_boundary():
    cfg, params, batch = _setup()
    outs = [tfm.forward(params, batch["tokens"], cfg, boundary=b)[0]
            for b in (0, 3, 6)]
    for o in outs[1:]:
        assert jnp.allclose(outs[0].astype(jnp.float32),
                            o.astype(jnp.float32), atol=1e-2)


def test_grads_zero_below_boundary_nonzero_above():
    cfg, params, batch = _setup()
    # make adapters non-trivial so grads flow
    e = params["blocks"][0]["adapter"]
    e["w_up"] = 0.02 * jax.random.normal(jax.random.key(9), e["w_up"].shape,
                                         jnp.float32).astype(e["w_up"].dtype)
    b = 3

    def loss_fn(tr):
        logits, _ = tfm.forward(params, batch["tokens"], cfg, boundary=b,
                                hot_adapters=tr["adapters"],
                                head_params=tr["head"])
        return jnp.sum(logits.astype(jnp.float32) ** 2)

    tr = training.split_trainable(params, b)
    g = jax.grad(loss_fn)(tr)
    hot = g["adapters"][0]["w_up"]
    assert hot.shape[0] == cfg.repeats - b
    assert float(jnp.abs(hot).max()) > 0
    assert float(jnp.abs(g["head"]["w"]).max()) > 0


def test_frozen_adapter_is_identity():
    """Zero-init W_up => untouched adapters compute the identity (the paper's
    'deactivated' bottom adapters)."""
    from repro.core.adapter import apply_adapter
    D, m = 32, 8
    p = {"w_down": jax.random.normal(jax.random.key(0), (D, m), jnp.float32),
         "w_up": jnp.zeros((m, D), jnp.float32)}
    h = jax.random.normal(jax.random.key(1), (4, D), jnp.float32)
    assert jnp.array_equal(apply_adapter(p, h), h)
