"""Parameter definitions for every block kind.

A model's parameters are described *declaratively* as a pytree of :class:`PD`
(param-def) leaves. One definition tree serves three purposes:

  * ``materialize(defs, key)``   -> real initialized arrays (smoke tests / training)
  * ``abstract(defs)``           -> ShapeDtypeStruct stand-ins (multi-pod dry-run)
  * ``specs(defs, rules)``       -> PartitionSpec tree (pjit in_shardings)

which guarantees init / sharding / dry-run can never drift apart.

Layer stacking: for each layer-pattern entry ``(kind, count)`` the block's leaves are
stacked with leading dims ``[repeats, count, ...]`` (logical axes ``layers, layers``),
so the transformer scans over repeats (outer) and count (inner) with compact HLO.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro import sharding as sh

# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class PD:
    """Declarative parameter definition."""

    shape: Tuple[int, ...]
    logical: Tuple[Optional[str], ...]
    init: str = "normal"          # normal | zeros | ones | rwkv_decay | arange_log
    scale: Optional[float] = None  # stddev for normal; default fan-in
    dtype: Optional[str] = None    # override model dtype

    def __post_init__(self):
        assert len(self.shape) == len(self.logical), (self.shape, self.logical)


def _stack(defs: Any, repeats: int, count: int) -> Any:
    """Prepend [repeats, count] stacking dims to every PD leaf."""

    def f(pd: PD) -> PD:
        return PD((repeats, count) + pd.shape, ("layers", "layers") + pd.logical,
                  pd.init, pd.scale, pd.dtype)

    return jax.tree.map(f, defs, is_leaf=lambda x: isinstance(x, PD))


# ---------------------------------------------------------------------------
# Shared sub-modules
# ---------------------------------------------------------------------------


def norm_defs(cfg: ModelConfig) -> Dict[str, PD]:
    d = {"scale": PD((cfg.d_model,), ("norm",), "ones", dtype="float32")}
    if cfg.norm == "layernorm":
        d["bias"] = PD((cfg.d_model,), ("norm",), "zeros", dtype="float32")
    return d


def adapter_defs(cfg: ModelConfig) -> Dict[str, PD]:
    """The paper's serial adapter: h <- h + sigma(h Wd) Wu  (eq. 1)."""
    m = cfg.adapter.bottleneck
    return {
        "w_down": PD((cfg.d_model, m), ("embed", "bottleneck")),
        "w_up": PD((m, cfg.d_model), ("bottleneck", "embed"),
                   "zeros" if cfg.adapter.zero_init_up else "normal"),
    }


def attn_defs(cfg: ModelConfig, cross: bool = False) -> Dict[str, PD]:
    D, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    d = {
        "wq": PD((D, H, hd), ("embed", "heads", "head_dim")),
        "wk": PD((D, K, hd), ("embed", "kv_heads", "head_dim")),
        "wv": PD((D, K, hd), ("embed", "kv_heads", "head_dim")),
        "wo": PD((H, hd, D), ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias and not cross:
        d["bq"] = PD((H, hd), ("heads", "head_dim"), "zeros")
        d["bk"] = PD((K, hd), ("kv_heads", "head_dim"), "zeros")
        d["bv"] = PD((K, hd), ("kv_heads", "head_dim"), "zeros")
    return d


def ffn_defs(cfg: ModelConfig) -> Dict[str, PD]:
    D, F = cfg.d_model, cfg.d_ff
    if cfg.glu:
        return {
            "w_gate": PD((D, F), ("embed", "ffn")),
            "w_up": PD((D, F), ("embed", "ffn")),
            "w_down": PD((F, D), ("ffn", "embed")),
        }
    d = {
        "w_in": PD((D, F), ("embed", "ffn")),
        "w_out": PD((F, D), ("ffn", "embed")),
    }
    if cfg.norm == "layernorm":  # BERT-era archs carry FFN biases
        d["b_in"] = PD((F,), ("ffn",), "zeros")
        d["b_out"] = PD((D,), ("embed",), "zeros")
    return d


def moe_defs(cfg: ModelConfig) -> Dict[str, PD]:
    m = cfg.moe
    D, E, F = cfg.d_model, m.n_experts, m.d_expert
    # expert weights: FSDP the d_model dim (400B scale) or keep expert-sharded
    # only (small experts; avoids the per-layer FSDP all-gather)
    ed = "embed" if m.fsdp_experts else "expert_embed"
    d = {
        "router": PD((D, E), ("embed", "experts"), scale=0.02),
        "we_gate": PD((E, D, F), ("experts", ed, "expert_ffn")),
        "we_up": PD((E, D, F), ("experts", ed, "expert_ffn")),
        "we_down": PD((E, F, D), ("experts", "expert_ffn", ed)),
    }
    if getattr(m, "n_shared", 0):
        pass  # shared experts folded into w_shared below when configured
    # one shared expert (DeepSeek/Llama-4 style) — always present for moe blocks
    d["ws_gate"] = PD((D, F), ("embed", "ffn"))
    d["ws_up"] = PD((D, F), ("embed", "ffn"))
    d["ws_down"] = PD((F, D), ("ffn", "embed"))
    return d


def rwkv_defs(cfg: ModelConfig) -> Dict[str, PD]:
    """RWKV-6 (Finch): data-dependent token-shift + decay via LoRA."""
    D = cfg.d_model
    hd = cfg.ssm.head_dim
    H = D // hd
    lora = cfg.ssm.decay_lora
    F = cfg.d_ff
    return {
        # --- time mix ---
        "mu": PD((5, D), (None, "embed"), "normal", scale=0.02),     # r,k,v,w,g base mix
        "tm_w1": PD((D, 5 * 32), ("embed", None), scale=0.02),       # ddlerp lora A
        "tm_w2": PD((5, 32, D), (None, "lora", "embed"), scale=0.02),
        "dd_w1": PD((D, lora), ("embed", "lora"), scale=0.02),       # decay lora A
        "dd_w2": PD((lora, D), ("lora", "embed"), scale=0.02),
        "decay_base": PD((H, hd), ("heads", "head_dim"), "rwkv_decay"),
        "bonus_u": PD((H, hd), ("heads", "head_dim"), "normal", scale=0.5),
        "wr": PD((D, H, hd), ("embed", "heads", "head_dim")),
        "wk": PD((D, H, hd), ("embed", "heads", "head_dim")),
        "wv": PD((D, H, hd), ("embed", "heads", "head_dim")),
        "wg": PD((D, H, hd), ("embed", "heads", "head_dim")),
        "wo": PD((H, hd, D), ("heads", "head_dim", "embed")),
        "ln_x": PD((D,), ("norm",), "ones", dtype="float32"),        # group-norm scale
        # --- channel mix ---
        "mu_ck": PD((D,), ("embed",), "normal", scale=0.02),
        "mu_cr": PD((D,), ("embed",), "normal", scale=0.02),
        "wk_c": PD((D, F), ("embed", "ffn")),
        "wv_c": PD((F, D), ("ffn", "embed")),
        "wr_c": PD((D, D), ("embed", "act_embed")),
    }


def mamba_defs(cfg: ModelConfig) -> Dict[str, PD]:
    """Mamba-style selective SSM head bank (the SSM half of a Hymba block)."""
    D = cfg.d_model
    di = cfg.n_heads * cfg.head_dim          # d_inner matches attention width
    N = cfg.ssm.state_size
    R = cfg.ssm.dt_rank
    W = cfg.ssm.conv_width
    return {
        "in_proj": PD((D, di), ("embed", "heads")),
        "conv_w": PD((W, di), ("conv", "heads"), "normal", scale=0.2),
        "x_proj": PD((di, R + 2 * N), ("heads", None)),
        "dt_proj": PD((R, di), ("lora", "heads"), scale=0.1),
        "dt_bias": PD((di,), ("heads",), "zeros"),
        "a_log": PD((di, N), ("heads", "state"), "arange_log"),
        "d_skip": PD((di,), ("heads",), "ones"),
    }


# ---------------------------------------------------------------------------
# Block kinds
# ---------------------------------------------------------------------------


def block_defs(cfg: ModelConfig, kind: str, causal: bool = True) -> Dict[str, Any]:
    if kind == "dense":
        return {
            "ln1": norm_defs(cfg), "attn": attn_defs(cfg),
            "ln2": norm_defs(cfg), "ffn": ffn_defs(cfg),
            "adapter": adapter_defs(cfg),
        }
    if kind == "moe":
        return {
            "ln1": norm_defs(cfg), "attn": attn_defs(cfg),
            "ln2": norm_defs(cfg), "moe": moe_defs(cfg),
            "adapter": adapter_defs(cfg),
        }
    if kind == "cross":
        return {
            "ln1": norm_defs(cfg), "attn": attn_defs(cfg),
            "lnx": norm_defs(cfg), "xattn": attn_defs(cfg, cross=True),
            "xgate": PD((1,), (None,), "zeros"),   # tanh-gated cross-attn (llama-3.2V)
            "ln2": norm_defs(cfg), "ffn": ffn_defs(cfg),
            "adapter": adapter_defs(cfg),
        }
    if kind == "rwkv":
        return {
            "ln1": norm_defs(cfg), "ln2": norm_defs(cfg),
            "rwkv": rwkv_defs(cfg),
            "adapter": adapter_defs(cfg),
        }
    if kind == "hymba":
        di = cfg.n_heads * cfg.head_dim
        return {
            "ln1": norm_defs(cfg),
            "attn": attn_defs(cfg),
            "ssm": mamba_defs(cfg),
            "norm_attn": PD((di,), ("heads",), "ones", dtype="float32"),
            "norm_ssm": PD((di,), ("heads",), "ones", dtype="float32"),
            "ln2": norm_defs(cfg), "ffn": ffn_defs(cfg),
            "adapter": adapter_defs(cfg),
        }
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Full model
# ---------------------------------------------------------------------------


def param_defs(cfg: ModelConfig) -> Dict[str, Any]:
    defs: Dict[str, Any] = {
        "embed": {"tok": PD((cfg.padded_vocab, cfg.d_model), ("vocab", "embed"),
                            scale=0.02)},
        "final_norm": norm_defs(cfg),
        "head": {"w": PD((cfg.d_model, cfg.out_dim),
                         ("embed", "vocab" if cfg.head_out is None else None))},
    }
    if not cfg.rope:
        defs["embed"]["pos"] = PD((min(cfg.max_seq_len, 8192), cfg.d_model),
                                  ("pos", "embed"), scale=0.02)
    if any(k == "hymba" for k, _ in cfg.pattern):
        defs["meta"] = PD((128, cfg.d_model), ("pos", "embed"), scale=0.02)
    # decoder (or the only) stack: tuple aligned with cfg.pattern
    defs["blocks"] = tuple(
        _stack(block_defs(cfg, kind), cfg.repeats, count)
        for kind, count in cfg.pattern
    )
    if cfg.enc_dec:
        enc_cfg = dataclasses.replace(cfg, qkv_bias=False)
        defs["encoder"] = {
            "blocks": (_stack(block_defs(enc_cfg, "dense"), cfg.n_enc_layers, 1),),
            "final_norm": norm_defs(cfg),
        }
    return defs


# ---------------------------------------------------------------------------
# Materialization / abstraction
# ---------------------------------------------------------------------------

_IS_PD = lambda x: isinstance(x, PD)


def _init_leaf(pd: PD, key: jax.Array, dtype: jnp.dtype) -> jax.Array:
    dt = jnp.dtype(pd.dtype) if pd.dtype else dtype
    if pd.init == "zeros":
        return jnp.zeros(pd.shape, dt)
    if pd.init == "ones":
        return jnp.ones(pd.shape, dt)
    if pd.init == "rwkv_decay":
        # per-channel decay prior in (-6, -0.5): w = exp(-exp(x))
        n = int(np.prod(pd.shape))
        v = jnp.linspace(-6.0, -0.5, n, dtype=jnp.float32).reshape(pd.shape)
        return v.astype(dt)
    if pd.init == "arange_log":
        # mamba A init: -[1..N] broadcast over channels, stored as log
        N = pd.shape[-1]
        a = jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32), pd.shape)
        return jnp.log(a).astype(dt)
    # normal with fan-in default
    fan_in = pd.shape[-2] if len(pd.shape) >= 2 else pd.shape[-1]
    # stacked leaves: ignore the two leading layer dims when inferring fan-in
    if pd.logical[:2] == ("layers", "layers") and len(pd.shape) >= 4:
        fan_in = pd.shape[-2]
    scale = pd.scale if pd.scale is not None else 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, pd.shape, jnp.float32) * scale).astype(dt)


def materialize(defs: Any, key: jax.Array, dtype: str = "bfloat16") -> Any:
    """Seeded initial values for ``defs``, computed as ONE jitted program.

    Eager and jitted initializers round some leaves differently, so every
    caller goes through the same program: a caller that traces this inside
    its own jit (``pipeline.init_stage_stack`` builds the stage-stacked
    weights directly in their sharding) gets bit-identical values."""
    leaves, treedef = jax.tree.flatten(defs, is_leaf=_IS_PD)
    return jax.tree.unflatten(treedef,
                              _init_leaves(tuple(leaves), key,
                                           jnp.dtype(dtype).name))


@functools.partial(jax.jit, static_argnums=(0, 2))
def _init_leaves(pds: Tuple[PD, ...], key: jax.Array, dtype: str) -> list:
    keys = jax.random.split(key, len(pds))
    dt = jnp.dtype(dtype)
    return [_init_leaf(pd, k, dt) for pd, k in zip(pds, keys)]


def abstract(defs: Any, dtype: str = "bfloat16") -> Any:
    dt = jnp.dtype(dtype)

    def f(pd: PD):
        return jax.ShapeDtypeStruct(pd.shape, jnp.dtype(pd.dtype) if pd.dtype else dt)

    return jax.tree.map(f, defs, is_leaf=_IS_PD)


def specs(defs: Any, rules: Dict[str, Any]) -> Any:
    return jax.tree.map(lambda pd: sh.spec_for(pd.logical, rules, pd.shape),
                        defs, is_leaf=_IS_PD)


def count_params(defs: Any, active_only: bool = False) -> int:
    total = 0
    for pd in jax.tree.leaves(defs, is_leaf=_IS_PD):
        n = int(np.prod(pd.shape))
        total += n
    return total


def count_active_params(cfg: ModelConfig) -> int:
    """Params touched per token: routed experts count as top_k (+ shared) of E."""
    defs = param_defs(cfg)
    total = 0
    for pd in jax.tree.leaves(defs, is_leaf=_IS_PD):
        n = int(np.prod(pd.shape))
        if "experts" in pd.logical and cfg.moe is not None:
            n = int(n * cfg.moe.top_k / cfg.moe.n_experts)
        total += n
    return total


def trainable_mask(defs: Any) -> Any:
    """PEFT mask: True for adapter + head leaves (the paper's trainable set)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        defs, is_leaf=_IS_PD)
    out = []
    for path, pd in flat:
        names = [getattr(p, "key", getattr(p, "idx", None)) for p in path]
        is_tr = ("adapter" in names) or ("head" in names)
        out.append(is_tr)
    return jax.tree.unflatten(treedef, out)
