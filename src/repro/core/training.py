"""Training and serving steps with RingAda's truncated backpropagation.

``split_trainable`` / ``merge_trainable`` realize the paper's trainable set: the
head plus every adapter above the unfreeze boundary. Gradients are taken *only*
with respect to that set, so XLA emits

  * no backward at all for the frozen trunk (stop_gradient scan split), and
  * no weight-gradient einsums for frozen backbone matrices in the hot region

— the two compute savings RingAda's early-stopped backpropagation provides.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from repro import scopes
from repro.configs.base import ModelConfig, TrainConfig
from repro.models import transformer as tfm
from repro.models.losses import cross_entropy, qa_span_loss
from repro.optim import adamw

Array = jax.Array


# ---------------------------------------------------------------------------
# Trainable split / merge
# ---------------------------------------------------------------------------


def split_trainable(params: Dict[str, Any], boundary: int) -> Dict[str, Any]:
    """Extract the differentiated leaves: hot adapter rows [b:] + head."""
    return {
        "adapters": tuple(jax.tree.map(lambda x: x[boundary:], e["adapter"])
                          for e in params["blocks"]),
        "head": params["head"],
    }


def full_trainable(params: Dict[str, Any]) -> Dict[str, Any]:
    """boundary=0 view — used to size optimizer state once."""
    return split_trainable(params, 0)


def merge_trainable(params: Dict[str, Any], trainable: Dict[str, Any],
                    boundary: int) -> Dict[str, Any]:
    """Rebuild the full param tree with hot adapter rows taken from ``trainable``."""
    blocks = []
    for e, hot in zip(params["blocks"], trainable["adapters"]):
        frozen = jax.tree.map(lambda x: lax.stop_gradient(x[:boundary]),
                              e["adapter"])
        ad = jax.tree.map(lambda f, h: jnp.concatenate([f, h], axis=0),
                          frozen, hot)
        blocks.append({**e, "adapter": ad})
    return {**params, "blocks": tuple(blocks), "head": trainable["head"]}


def write_back(params: Dict[str, Any], new_trainable_full: Dict[str, Any],
               ) -> Dict[str, Any]:
    """Install a full-size trainable tree (adapters [R,...] + head) into params."""
    blocks = tuple({**e, "adapter": ad}
                   for e, ad in zip(params["blocks"],
                                    new_trainable_full["adapters"]))
    return {**params, "blocks": blocks, "head": new_trainable_full["head"]}


def slice_to_full(params: Dict[str, Any], trainable_sliced: Dict[str, Any],
                  boundary: int) -> Dict[str, Any]:
    """Merge sliced hot rows with the existing frozen rows -> full-size tree."""
    ads = []
    for e, hot in zip(params["blocks"], trainable_sliced["adapters"]):
        ads.append(jax.tree.map(
            lambda x, h: jnp.concatenate([x[:boundary], h], axis=0),
            e["adapter"], hot))
    return {"adapters": tuple(ads), "head": trainable_sliced["head"]}


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------


def make_train_step(cfg: ModelConfig, tc: TrainConfig, boundary: int, *,
                    impl: str = "jnp", with_memory: bool = False,
                    remat: bool = False, act_spec=None, moe_groups: int = 1):
    """Build a (jit-able) train step for a *static* unfreeze boundary.

    batch: {"tokens": [B,S] i32, "labels": [B,S] i32, optional "mask" [B,S],
            optional "memory": [B,T,D]}
    """

    def train_step(params, opt_state, batch):
        trainable = split_trainable(params, boundary)

        def loss_fn(tr):
            logits, aux = tfm.forward(params, batch["tokens"], cfg,
                                      memory=batch.get("memory"),
                                      boundary=boundary, impl=impl,
                                      remat=remat, act_spec=act_spec,
                                      moe_groups=moe_groups,
                                      hot_adapters=tr["adapters"],
                                      head_params=tr["head"])
            ce_chunk = 512 if cfg.out_dim >= 32768 else None
            with jax.named_scope(scopes.HEAD):
                loss, metrics = cross_entropy(logits, batch["labels"],
                                              batch.get("mask"), chunk=ce_chunk)
            metrics = {**metrics,
                       **{k: lax.stop_gradient(v) for k, v in aux.items()}}
            return loss, metrics

        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            trainable)
        with jax.named_scope(scopes.OPTIMIZER):
            tr_full = slice_to_full(params, trainable, boundary)
            new_tr_full, new_opt = adamw.update(grads, opt_state, tr_full, tc,
                                                boundary)
            new_params = write_back(params, new_tr_full)
            gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                                 for g in jax.tree.leaves(grads)))
        metrics = {**metrics, "grad_norm": gnorm}
        return new_params, new_opt, metrics

    return train_step


def make_qa_train_step(cfg: ModelConfig, tc: TrainConfig, boundary: int, *,
                       impl: str = "jnp"):
    """SQuAD-style span-extraction step (the paper's task): batch carries
    {"tokens" [B,S], "starts" [B], "ends" [B]}; the head emits [B,S,2]."""
    assert cfg.head_out == 2, "qa step needs a span head (head_out=2)"

    def train_step(params, opt_state, batch):
        trainable = split_trainable(params, boundary)

        def loss_fn(tr):
            logits, _ = tfm.forward(params, batch["tokens"], cfg,
                                    boundary=boundary, impl=impl,
                                    hot_adapters=tr["adapters"],
                                    head_params=tr["head"])
            with jax.named_scope(scopes.HEAD):
                return qa_span_loss(logits, batch["starts"], batch["ends"])

        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            trainable)
        with jax.named_scope(scopes.OPTIMIZER):
            tr_full = slice_to_full(params, trainable, boundary)
            new_tr_full, new_opt = adamw.update(grads, opt_state, tr_full, tc,
                                                boundary)
            new_params = write_back(params, new_tr_full)
        return new_params, new_opt, metrics

    return train_step


def make_step(cfg: ModelConfig, tc: TrainConfig, boundary: int, *,
              impl: str = "jnp"):
    """Task-dispatching step builder: QA span head vs LM objective.

    The single entry point the session API (``repro.api``) and the launch
    driver share, so "which step fn does this config train with" is decided in
    exactly one place.
    """
    if cfg.head_out == 2:
        return make_qa_train_step(cfg, tc, boundary, impl=impl)
    return make_train_step(cfg, tc, boundary, impl=impl)


def make_eval_step(cfg: ModelConfig, *, impl: str = "jnp"):
    def eval_step(params, batch):
        logits, _ = tfm.forward(params, batch["tokens"], cfg,
                                memory=batch.get("memory"), impl=impl)
        loss, metrics = cross_entropy(logits, batch["labels"],
                                      batch.get("mask"))
        return metrics

    return eval_step


# ---------------------------------------------------------------------------
# Serve steps
# ---------------------------------------------------------------------------


def make_prefill_step(cfg: ModelConfig, seq_len: int, *, impl: str = "jnp",
                      act_spec=None, moe_groups: int = 1):
    def prefill_step(params, tokens, memory=None):
        return tfm.prefill(params, tokens, cfg, memory=memory,
                           seq_len=seq_len, impl=impl, act_spec=act_spec,
                           moe_groups=moe_groups)

    return prefill_step


def make_serve_step(cfg: ModelConfig, *, impl: str = "jnp", greedy: bool = True,
                    act_spec=None):
    """One-token decode: (params, cache, token) -> (next_token, logits, cache)."""

    def serve_step(params, token, cache):
        logits, new_cache = tfm.decode_step(params, token, cache, cfg, impl=impl,
                                            act_spec=act_spec)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        return nxt, logits, new_cache

    return serve_step
