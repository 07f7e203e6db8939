"""The frozen trunk's attention through the flash kernel.

``models.transformer.forward`` marks the blocks below the unfreeze boundary
as ``primal_only``; their causal self-attention takes the Pallas flash
kernel where the step is lowered for a TPU and ``_attend`` elsewhere.  Here
the kernel path is forced in interpret mode by patching that choice, and
the pjit step is compared with the plain jnp step; the step's jaxpr holds
the kernel in the trunk's forward only, never differentiated.
"""
import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import TrainConfig, get_config
from repro.core import training
from repro.kernels import flash_attention as fak
from repro.models import blocks
from repro.models import params as prm
from repro.optim import adamw

B, S, BLOCK = 2, 256, 64


def _setup(dtype="bfloat16"):
    cfg = get_config("stablelm-3b").reduced(n_layers=4, repeats=4, d_model=160,
                                            d_ff=256, head_dim=80, dtype=dtype)
    tc = TrainConfig(batch_size=B, seq_len=S)
    params = prm.materialize(prm.param_defs(cfg), jax.random.key(0), cfg.dtype)
    opt = adamw.init(training.full_trainable(params))
    toks = jax.random.randint(jax.random.key(1), (B, S + 1), 0, cfg.vocab_size)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    return cfg, tc, params, opt, batch


def _force_kernel(monkeypatch):
    """The choice a TPU lowering makes, in interpret mode, at 64-token
    blocks: four key blocks a row, so blocks are skipped and masked."""
    monkeypatch.setattr(blocks, "FLASH_BLOCK", BLOCK)
    monkeypatch.setattr(
        blocks, "_attend_primal",
        lambda q, k, v, positions, *, window, q_chunk: blocks._flash(
            q, k, v, window=window, interpret=True))


def _kernels(jaxpr, found):
    """Every pallas_call equation in a jaxpr and its sub-jaxprs."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else [param]:
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    _kernels(sub.jaxpr, found)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    _kernels(sub, found)
    return found


def test_pjit_step_with_trunk_kernel_matches_jnp(monkeypatch):
    """Boundary 2 of 4, in float32: loss, first moments (the gradient times
    1 - beta1) and updated trainables of one step match the jnp step.  In
    bf16 this toy's gradients are mostly rounding noise (bf16 against
    float32 jnp steps differ by more than their size), so the comparison is
    made where the two attentions differ only by float32 summation order;
    the kernel's bf16 rounding is pinned in ``test_kernels.py``."""
    cfg, tc, params, opt, batch = _setup("float32")
    step = training.make_train_step(cfg, tc, 2)
    want = jax.jit(step)(params, opt, batch)
    _force_kernel(monkeypatch)
    got = jax.jit(training.make_train_step(cfg, tc, 2))(params, opt, batch)
    (p_w, o_w, m_w), (p_g, o_g, m_g) = want, got
    # readings on this toy: loss 2e-7 relative, moments 4e-4 of the largest,
    # trainables 5e-6; the limits leave about ten times that
    np.testing.assert_allclose(float(m_g["loss"]), float(m_w["loss"]),
                               rtol=2e-6)
    for a, b in zip(jax.tree.leaves(o_g["m"]), jax.tree.leaves(o_w["m"])):
        a, b = np.asarray(a), np.asarray(b)
        assert np.abs(a - b).max() <= 4e-3 * np.abs(b).max(), (
            np.abs(a - b).max(), np.abs(b).max())
    new = training.full_trainable
    for a, b in zip(jax.tree.leaves(new(p_g)), jax.tree.leaves(new(p_w))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


@pytest.mark.parametrize("forced", [False, True])
@pytest.mark.parametrize("boundary", [0, 2])
def test_step_holds_kernel_only_in_primal_trunk(boundary, forced, monkeypatch):
    """Traced as any platform sees it (both sides of the platform choice)
    or forced: the kernel appears once, in the trunk's block body, with
    q, k, v in and one output out.  A differentiated kernel would carry
    tangents (six inputs, two outputs); at boundary 0 there is no trunk
    and no kernel."""
    cfg, tc, params, opt, batch = _setup()
    if forced:
        _force_kernel(monkeypatch)
    jaxpr = jax.make_jaxpr(training.make_train_step(cfg, tc, boundary))(
        params, opt, batch)
    found = _kernels(jaxpr.jaxpr, [])
    assert len(found) == (1 if boundary else 0), found
    for eqn in found:
        assert eqn.params["name"] == fak.NAME
        assert (len(eqn.invars), len(eqn.outvars)) == (3, 1)
