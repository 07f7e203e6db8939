"""Forward computation for every block kind.

All block functions share the signature::

    new_h, new_cache, aux = apply_block(kind, cfg, params, h, ctx)

where ``ctx`` is a :class:`BlockCtx` carrying mode ("seq" for train/prefill over a full
sequence, "step" for single-token decode), positions, the per-layer cache slice, and
optional cross-attention memory. Shapes:

    h          [B, S, D]          (S == 1 in "step" mode)
    cache      per-kind dict, see repro.models.kvcache
    memory     [B, T_mem, D]      (VLM patches / audio frames / encoder output)

Attention is computed with a query-chunked scan so no [S, S] score tensor is ever
materialized (required for the 32k prefill shape), with optional sliding windows and
attention-sink slots (Hymba meta tokens).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro import scopes
from repro.configs.base import ModelConfig
from repro.core.adapter import apply_adapter
from repro.kernels.flash_attention import flash_attention

Array = jax.Array


def _chunk_of(n: int, cap: int) -> int:
    """Largest divisor of n that is <= cap."""
    best = 1
    for d in range(1, min(n, cap) + 1):
        if n % d == 0:
            best = d
    return best


# ---------------------------------------------------------------------------
# Context
# ---------------------------------------------------------------------------


@dataclass
class BlockCtx:
    cfg: ModelConfig
    mode: str                       # "seq" | "step"
    positions: Array                # [B, S] absolute token positions
    causal: bool = True
    memory: Optional[Array] = None  # [B, T_mem, D]
    cache_positions: Optional[Array] = None   # [B, Ck] positions held in cache
    write_slots: Optional[Array] = None       # [B, S] cache slots for new tokens
    impl: str = "jnp"               # "jnp" | "pallas"
    q_chunk: int = 1024
    remat: bool = False             # per-block activation checkpointing
    act_spec: Any = None            # PartitionSpec pinned on the residual stream
    moe_groups: int = 1             # GShard group-local dispatch groups
    # no tangent reaches these blocks (the frozen trunk under stop_gradient):
    # set by transformer.forward only, never a user's choice
    primal_only: bool = False


# ---------------------------------------------------------------------------
# Primitives
# ---------------------------------------------------------------------------


def rmsnorm(p: Dict[str, Array], x: Array) -> Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * lax.rsqrt(var + 1e-6) * p["scale"]
    return out.astype(x.dtype)


def layernorm(p: Dict[str, Array], x: Array) -> Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * lax.rsqrt(var + 1e-5) * p["scale"] + p.get("bias", 0.0)
    return out.astype(x.dtype)


def norm(cfg: ModelConfig, p, x):
    return layernorm(p, x) if cfg.norm == "layernorm" else rmsnorm(p, x)


def _ffn_act(cfg: ModelConfig):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu,
            "relu": jax.nn.relu}[cfg.activation]


def ffn(cfg: ModelConfig, p: Dict[str, Array], x: Array) -> Array:
    act = _ffn_act(cfg)
    if cfg.glu:
        return (act(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]
    h = x @ p["w_in"]
    if "b_in" in p:
        h = h + p["b_in"].astype(x.dtype)
    h = act(h)
    out = h @ p["w_out"]
    if "b_out" in p:
        out = out + p["b_out"].astype(x.dtype)
    return out


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope(x: Array, positions: Array, theta: float, *,
         heads_first: bool = False) -> Array:
    """x: [B, S, H, hd] ([B, H, S, hd] if ``heads_first``); positions: [B, S]."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs          # [B, S, half]
    at = (lambda a: a[:, None]) if heads_first else (lambda a: a[:, :, None])
    cos = at(jnp.cos(ang))
    sin = at(jnp.sin(ang))
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention core: chunked-query attention against a (possibly cached) KV set
# ---------------------------------------------------------------------------


def _attend(q: Array, k: Array, v: Array, q_pos: Array, k_pos: Array, *,
            causal: bool, window: Optional[int], n_sink: int,
            q_chunk: int, score_spec=None) -> Array:
    """q [B,Sq,H,hd]; k,v [B,Sk,K,hd]; *_pos absolute positions ([B,S*]).

    Returns [B, Sq, H, hd]. Never materializes more than [B, H, q_chunk, Sk]
    scores; each q-chunk is rematerialized in the backward (flash-style — the
    fp32 score tensor is never a residual). ``score_spec`` (a PartitionSpec for
    [B, K, G, c, Sk]) sequence-shards the scores when heads don't divide the
    tensor axis (e.g. 40 heads on model=16). ``k_pos`` may contain -1 for
    unwritten cache slots.
    """
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, Sq, K, G, hd)

    def mask_for(qp, kp, k_slot):
        # qp [B, c] ; kp [B, Sk]
        m = kp[:, None, :] >= 0
        if causal:
            m &= kp[:, None, :] <= qp[:, :, None]
        if window is not None:
            in_win = (qp[:, :, None] - kp[:, None, :]) < window
            if n_sink > 0:
                in_win |= k_slot[None, None, :] < n_sink
            m &= in_win
        return m                                                     # [B, c, Sk]

    k_slot = jnp.arange(k.shape[1], dtype=jnp.int32)

    def chunk_fn(qc, qpc, k, v, k_pos):
        # qc [B, c, K, G, hd]
        s = jnp.einsum("bckgh,bskh->bkgcs", qc, k,
                       preferred_element_type=jnp.float32) * scale
        if score_spec is not None:
            s = lax.with_sharding_constraint(s, score_spec)
        m = mask_for(qpc, k_pos, k_slot)                             # [B, c, Sk]
        s = jnp.where(m[:, None, None, :, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        # fully-masked rows (e.g. pos<0 padding) -> zeros
        p = jnp.where(m[:, None, None, :, :], p, 0.0).astype(v.dtype)
        return jnp.einsum("bkgcs,bskh->bckgh", p, v)

    if Sq > 1:
        # flash-style: recompute scores in the backward instead of stashing
        # the [B, H, c, Sk] fp32 score / bool mask tensors per chunk.
        chunk_fn = jax.checkpoint(chunk_fn)

    with jax.named_scope(scopes.ATTENTION):
        if Sq <= q_chunk:
            out = chunk_fn(qg, q_pos, k, v, k_pos)
        else:
            assert Sq % q_chunk == 0, (Sq, q_chunk)
            nc = Sq // q_chunk
            qs = qg.reshape(B, nc, q_chunk, K, G, hd).transpose(1, 0, 2, 3, 4, 5)
            ps = q_pos.reshape(B, nc, q_chunk).transpose(1, 0, 2)
            out = lax.map(lambda args: chunk_fn(args[0], args[1], k, v, k_pos),
                          (qs, ps))
            out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, K, G, hd)
    return out.reshape(B, Sq, H, hd)


# query and key block of the flash kernel (see kernels/flash_attention.py)
FLASH_BLOCK = 512


def _flash(q: Array, k: Array, v: Array, *, window: Optional[int],
           interpret: bool = False) -> Array:
    """Causal self-attention through the flash kernel. q [B,H,S,hd];
    k,v [B,K,S,hd] at positions 0..S-1. Returns [B, H, S, hd]."""
    blk = min(FLASH_BLOCK, q.shape[2])
    return flash_attention(q, k, v, causal=True, window=window, block_q=blk,
                           block_k=blk, interpret=interpret)


def _attend_primal(q: Array, k: Array, v: Array, positions: Array, *,
                   window: Optional[int], q_chunk: int) -> Array:
    """Causal self-attention that is never differentiated, heads first
    ([B, H, S, hd]): the flash kernel where the program is lowered for a
    TPU, ``_attend`` elsewhere."""
    def plain(q, k, v, positions):
        seq = lambda t: t.transpose(0, 2, 1, 3)
        return seq(_attend(seq(q), seq(k), seq(v), positions, positions,
                           causal=True, window=window, n_sink=0,
                           q_chunk=q_chunk))

    def kernel(q, k, v, positions):
        with jax.named_scope(scopes.ATTENTION):
            return _flash(q, k, v, window=window)

    return lax.platform_dependent(q, k, v, positions, tpu=kernel,
                                  default=plain)


def _attention_primal(cfg: ModelConfig, p: Dict[str, Array], x: Array,
                      ctx: BlockCtx) -> Array:
    """``attention`` of a block that no tangent reaches, without a cache: the
    projections emit the kernel's [B, H, S, hd] layout directly."""
    proj = lambda w: jnp.einsum("bsd,dhk->bhsk", x, w)
    q, kk, vv = proj(p["wq"]), proj(p["wk"]), proj(p["wv"])
    if "bq" in p:
        q, kk, vv = (t + b[:, None] for t, b in
                     ((q, p["bq"]), (kk, p["bk"]), (vv, p["bv"])))
    if cfg.rope:
        q = rope(q, ctx.positions, cfg.rope_theta, heads_first=True)
        kk = rope(kk, ctx.positions, cfg.rope_theta, heads_first=True)
    out = _attend_primal(q, kk, vv, ctx.positions, window=cfg.sliding_window,
                         q_chunk=ctx.q_chunk)
    return jnp.einsum("bhsk,hkd->bsd", out.astype(x.dtype), p["wo"])


def attention(cfg: ModelConfig, p: Dict[str, Array], x: Array, ctx: BlockCtx,
              cache: Optional[Dict[str, Array]] = None,
              ) -> Tuple[Array, Optional[Dict[str, Array]]]:
    """Self-attention with optional KV cache (decode) and sliding window."""
    B, S, D = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    n_sink = 128 if any(kind == "hymba" for kind, _ in cfg.pattern) else 0
    if (ctx.primal_only and ctx.mode == "seq" and cache is None and ctx.causal
            and n_sink == 0 and ctx.act_spec is None
            and S % min(FLASH_BLOCK, S) == 0):
        return _attention_primal(cfg, p, x, ctx), None
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    kk = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    vv = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if "bq" in p:
        q, kk, vv = q + p["bq"], kk + p["bk"], vv + p["bv"]
    if cfg.rope:
        q = rope(q, ctx.positions, cfg.rope_theta)
        kk = rope(kk, ctx.positions, cfg.rope_theta)

    new_cache = None

    def _quant(t):
        """Per-(token, head) int8 symmetric quantization: (q, scale)."""
        s = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1, keepdims=True)
        s = jnp.maximum(s, 1e-6) / 127.0
        q = jnp.clip(jnp.round(t.astype(jnp.float32) / s), -127, 127
                     ).astype(jnp.int8)
        return q, s.astype(x.dtype)

    def _dequant(q, s):
        return q.astype(x.dtype) * s

    if cache is None:
        k_use, v_use, k_pos = kk, vv, ctx.positions
    elif ctx.mode == "prefill":
        # gather-fill: ctx.write_slots is [B, Ck] = prompt index landing in each
        # cache slot (deterministic; no duplicate scatter). Attention itself runs
        # against the full freshly-projected kk/vv.
        gi = ctx.write_slots[..., None, None]
        gk = jnp.take_along_axis(kk, gi, axis=1)
        gv = jnp.take_along_axis(vv, gi, axis=1)
        if cfg.kv_quant:
            qk, sk = _quant(gk)
            qv, sv = _quant(gv)
            new_cache = {"k": qk, "v": qv, "k_s": sk, "v_s": sv}
        else:
            new_cache = {"k": gk.astype(cache["k"].dtype),
                         "v": gv.astype(cache["v"].dtype)}
        k_use, v_use, k_pos = kk, vv, ctx.positions
    else:
        # decode step: scatter the single new token at ctx.write_slots ([B, 1])
        b_idx = jnp.arange(B, dtype=jnp.int32)[:, None]
        ws = ctx.write_slots
        if cfg.kv_quant:
            qk, sk = _quant(kk)
            qv, sv = _quant(vv)
            new_cache = {
                "k": cache["k"].at[b_idx, ws].set(qk),
                "v": cache["v"].at[b_idx, ws].set(qv),
                "k_s": cache["k_s"].at[b_idx, ws].set(sk),
                "v_s": cache["v_s"].at[b_idx, ws].set(sv),
            }
            k_use = _dequant(new_cache["k"], new_cache["k_s"])
            v_use = _dequant(new_cache["v"], new_cache["v_s"])
        else:
            ck = cache["k"].at[b_idx, ws].set(kk.astype(cache["k"].dtype))
            cv = cache["v"].at[b_idx, ws].set(vv.astype(cache["v"].dtype))
            new_cache = {"k": ck, "v": cv}
            k_use, v_use = ck, cv
        k_pos = ctx.cache_positions

    score_spec = None
    if (ctx.act_spec is not None and len(ctx.act_spec) and S > 1
            and k_use.shape[1] % 16 == 0):
        from jax.sharding import PartitionSpec as P
        score_spec = P(ctx.act_spec[0], None, None, None, ctx.act_spec[-1])
    out = _attend(q, k_use, v_use, ctx.positions, k_pos,
                  causal=ctx.causal, window=cfg.sliding_window,
                  n_sink=n_sink, q_chunk=ctx.q_chunk, score_spec=score_spec)
    y = jnp.einsum("bshk,hkd->bsd", out.astype(x.dtype), p["wo"])
    return y, new_cache


def cross_attention(cfg: ModelConfig, p: Dict[str, Array], x: Array,
                    ctx: BlockCtx, cache: Optional[Dict[str, Array]] = None,
                    ) -> Tuple[Array, Optional[Dict[str, Array]]]:
    """Cross-attention against ctx.memory (or cached memory projections)."""
    B, S, D = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if cache is not None and "xk" in cache and ctx.memory is None:
        kk, vv = cache["xk"], cache["xv"]
        new_cache = cache
    else:
        mem = ctx.memory
        kk = jnp.einsum("btd,dhk->bthk", mem, p["wk"])
        vv = jnp.einsum("btd,dhk->bthk", mem, p["wv"])
        new_cache = {"xk": kk, "xv": vv} if cache is not None else None
    Tm = kk.shape[1]
    k_pos = jnp.zeros((B, Tm), dtype=jnp.int32)      # memory fully visible
    out = _attend(q, kk, vv, jnp.zeros_like(ctx.positions), k_pos,
                  causal=False, window=None, n_sink=0, q_chunk=ctx.q_chunk)
    y = jnp.einsum("bshk,hkd->bsd", out.astype(x.dtype), p["wo"])
    return y, new_cache


# ---------------------------------------------------------------------------
# Mixture of Experts (scatter-dispatch, capacity-bounded — see DESIGN.md §4)
# ---------------------------------------------------------------------------


def _moe_dispatch_group(cfg: ModelConfig, p: Dict[str, Array], xt: Array,
                        C: int) -> Tuple[Array, Array, Array]:
    """Capacity-bounded dispatch for one token group (all ops group-local).

    xt: [Tg, D]. Returns (routed_out [Tg, D], me [E], pe [E]) where me/pe feed
    the load-balance loss.
    """
    m = cfg.moe
    Tg, D = xt.shape
    E, kk = m.n_experts, m.top_k

    logits = (xt @ p["router"].astype(xt.dtype)).astype(jnp.float32)   # [Tg, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = lax.top_k(probs, kk)                                  # [Tg, k]
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)

    # rank of each (token, choice) within its expert via sort (no [T,E] cumsum)
    flat_e = eidx.reshape(Tg * kk)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    grp_start = jnp.searchsorted(sorted_e, jnp.arange(E, dtype=flat_e.dtype))
    rank_sorted = jnp.arange(Tg * kk, dtype=jnp.int32) - grp_start[sorted_e]
    ranks = jnp.zeros(Tg * kk, jnp.int32).at[order].set(rank_sorted)

    keep = ranks < C
    slot = jnp.where(keep, flat_e * C + ranks, E * C)                   # dummy

    xr = jnp.repeat(xt, kk, axis=0)                                     # [Tg*k, D]
    buf = jnp.zeros((E * C + 1, D), xt.dtype).at[slot].add(xr)
    xe = buf[: E * C].reshape(E, C, D)

    act = _ffn_act(cfg)
    hg = jnp.einsum("ecd,edf->ecf", xe, p["we_gate"])
    hu = jnp.einsum("ecd,edf->ecf", xe, p["we_up"])
    ye = jnp.einsum("ecf,efd->ecd", act(hg) * hu, p["we_down"])
    flat_out = jnp.concatenate(
        [ye.reshape(E * C, D), jnp.zeros((1, D), ye.dtype)], axis=0)
    tok_out = flat_out[slot] * (gates.reshape(Tg * kk, 1).astype(ye.dtype)
                                * keep[:, None])
    routed = tok_out.reshape(Tg, kk, D).sum(axis=1)

    me = jnp.mean(jax.nn.one_hot(eidx, E, dtype=jnp.float32), axis=(0, 1))
    pe = jnp.mean(probs, axis=0)
    zl = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return routed, me, pe, zl


def moe_ffn(cfg: ModelConfig, p: Dict[str, Array], x: Array,
            ctx: Optional["BlockCtx"] = None,
            ) -> Tuple[Array, Dict[str, Array]]:
    """x: [B, S, D] -> (out, aux losses).

    GShard-style *group-local* dispatch: tokens are split into
    ``ctx.moe_groups`` groups aligned with the data-parallel sharding, each
    group routes/scatters/combines locally (capacity per group), and only the
    expert einsums touch the expert-sharded weights. This keeps the dispatch
    buffers sharded [G('data'), E, C_g, D] with NO global scatter — the
    replicated [T*k*cf, D] buffer of the naive formulation (13+ GiB/chip at
    llama4 train_4k scale) never exists. See EXPERIMENTS.md §Perf.
    """
    m = cfg.moe
    B, S, D = x.shape
    T = B * S
    E, kk = m.n_experts, m.top_k
    G = 1
    if ctx is not None and getattr(ctx, "moe_groups", 1) > 1:
        G = ctx.moe_groups
        if T % G != 0:
            G = 1
    Tg = T // G
    C = int(math.ceil(Tg * kk / E * m.capacity_factor))
    C = max(8, -(-C // 8) * 8)

    xg = x.reshape(G, Tg, D)
    if G > 1 and ctx is not None and ctx.act_spec is not None:
        from jax.sharding import PartitionSpec as P
        xg = lax.with_sharding_constraint(xg, P(ctx.act_spec[0], None,
                                                ctx.act_spec[-1]))
    routed, me, pe, zl = jax.vmap(
        lambda xt: _moe_dispatch_group(cfg, p, xt, C))(xg)
    routed = routed.reshape(B, S, D)

    xt = x.reshape(T, D)
    act = _ffn_act(cfg)
    shared = (act(xt @ p["ws_gate"]) * (xt @ p["ws_up"])) @ p["ws_down"]
    out = routed + shared.reshape(B, S, D)

    aux = {
        "moe_aux": E * jnp.sum(me.mean(0) * pe.mean(0)) * m.router_aux_weight,
        "moe_z": jnp.mean(zl) * m.router_z_weight,
    }
    return out, aux


# ---------------------------------------------------------------------------
# RWKV-6 (Finch) — chunked parallel wkv with data-dependent decay
# ---------------------------------------------------------------------------


def _token_shift(x: Array, prev: Optional[Array]) -> Array:
    """x[t-1] (zeros / cached `prev` at t=0). x: [B, S, D], prev: [B, D]."""
    if x.shape[1] == 1:
        base = jnp.zeros_like(x[:, 0]) if prev is None else prev
        return base[:, None, :]
    shifted = jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    if prev is not None:
        shifted = shifted.at[:, 0].set(prev)
    return shifted


def _ddlerp(p, xx: Array, sx: Array) -> Tuple[Array, ...]:
    """RWKV6 data-dependent token-shift mixing -> (r,k,v,w,g) inputs."""
    base = xx + sx * p["mu"][0]
    lo = jnp.tanh(base @ p["tm_w1"]).reshape(*xx.shape[:-1], 5, 32)
    mws = jnp.einsum("bslr,lrd->bsld", lo, p["tm_w2"])                 # [B,S,5,D]
    outs = []
    for i in range(5):
        outs.append(xx + sx * (p["mu"][i] + mws[:, :, i].astype(xx.dtype)))
    return tuple(outs)


def _wkv_chunk(state: Array, r, k, v, lw, u):
    """One chunk of the RWKV6 recurrence (see DESIGN.md / kernels/rwkv_scan.py).

    state [N, hd, hd] fp32; r,k,v [N, L, hd]; lw = log decay (<=0) [N, L, hd].
    Returns (new_state, out [N, L, hd]).
    """
    N, L, hd = r.shape
    ca = jnp.cumsum(lw, axis=1)                     # inclusive log-decay prefix
    ca_prev = ca - lw                               # exclusive
    # inter-chunk: r_t decayed against incoming state
    inter = jnp.einsum("nlk,nkv->nlv", r * jnp.exp(ca_prev), state)
    # intra-chunk pairwise decays (all exponents <= 0: safe)
    diff = ca_prev[:, :, None, :] - ca[:, None, :, :]       # [N, L, L, hd]
    mask = jnp.tril(jnp.ones((L, L), bool), k=-1)[None, :, :, None]
    P = jnp.where(mask, jnp.exp(diff), 0.0)
    A = jnp.einsum("ntk,ntsk,nsk->nts", r, P, k)
    intra = jnp.einsum("nts,nsv->ntv", A, v)
    diag = jnp.sum(r * u * k, axis=-1, keepdims=True) * v   # current-token bonus
    out = inter + intra + diag
    # state update
    decay_all = jnp.exp(ca[:, -1])                          # [N, hd]
    carry_k = k * jnp.exp(ca[:, -1][:, None, :] - ca)       # prod_{u>s} w
    new_state = decay_all[:, :, None] * state + jnp.einsum(
        "nsk,nsv->nkv", carry_k, v)
    return new_state, out


def rwkv_time_mix(cfg: ModelConfig, p, x: Array,
                  cache: Optional[Dict[str, Array]],
                  impl: str = "jnp",
                  ) -> Tuple[Array, Optional[Dict[str, Array]]]:
    B, S, D = x.shape
    hd = cfg.ssm.head_dim
    H = D // hd
    prev = cache.get("px_tm") if cache else None
    xprev = _token_shift(x, prev)
    sx = xprev - x
    xr, xk, xv, xw, xg = _ddlerp(p, x, sx)

    r = (xr @ p["wr"].reshape(D, D)).reshape(B, S, H, hd)
    k = (xk @ p["wk"].reshape(D, D)).reshape(B, S, H, hd)
    v = (xv @ p["wv"].reshape(D, D)).reshape(B, S, H, hd)
    g = (xg @ p["wg"].reshape(D, D)).reshape(B, S, H, hd)
    dd = jnp.tanh(xw @ p["dd_w1"]) @ p["dd_w2"]                    # [B,S,D]
    wlog = p["decay_base"].reshape(1, 1, H, hd) + dd.reshape(B, S, H, hd)
    lw = -jnp.exp(wlog.astype(jnp.float32))                        # log decay <= 0
    u = p["bonus_u"].astype(jnp.float32)

    rf = r.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kf = k.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    vf = v.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    lwf = lw.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    uf = jnp.broadcast_to(u[None], (B, H, hd)).reshape(B * H, 1, hd)

    state0 = (cache["state"].reshape(B * H, hd, hd).astype(jnp.float32)
              if cache else jnp.zeros((B * H, hd, hd), jnp.float32))

    if impl == "pallas" and S > 1:
        from repro.kernels import ops
        out, state = ops.rwkv_scan(rf, kf, vf, lwf, uf, state0)
    elif S == 1:
        # single-step recurrence
        kv = jnp.einsum("nk,nv->nkv", kf[:, 0], vf[:, 0])
        out = (jnp.einsum("nk,nkv->nv", rf[:, 0], state0 + uf[:, 0, :, None] * kv)
               )[:, None, :]
        state = jnp.exp(lwf[:, 0])[:, :, None] * state0 + kv
    else:
        L = _chunk_of(S, 32)
        nchunks = S // L

        wkv = jax.checkpoint(_wkv_chunk)   # never stash the [L,L,hd] decays

        def body(st, idx):
            sl = lambda a: lax.dynamic_slice_in_dim(a, idx * L, L, axis=1)
            st2, out_c = wkv(st, sl(rf), sl(kf), sl(vf), sl(lwf),
                             uf[:, 0][:, None, :])
            return st2, out_c

        state, outs = lax.scan(body, state0, jnp.arange(nchunks))
        out = outs.transpose(1, 0, 2, 3).reshape(B * H, S, hd)

    out = out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)            # [B,S,H,hd]
    # per-head group-norm, then gate
    mu = jnp.mean(out, axis=-1, keepdims=True)
    var = jnp.var(out, axis=-1, keepdims=True)
    out = (out - mu) * lax.rsqrt(var + 1e-5)
    out = out.reshape(B, S, D) * p["ln_x"]
    out = out * jax.nn.silu(g.astype(jnp.float32)).reshape(B, S, D)
    y = (out.astype(x.dtype).reshape(B, S, H, hd))
    y = jnp.einsum("bshk,hkd->bsd", y, p["wo"])

    new_cache = None
    if cache is not None:
        new_cache = dict(cache)
        new_cache["state"] = state.reshape(B, H, hd, hd).astype(cache["state"].dtype)
        new_cache["px_tm"] = x[:, -1]
    return y, new_cache


def rwkv_channel_mix(cfg: ModelConfig, p, x: Array,
                     cache: Optional[Dict[str, Array]],
                     ) -> Tuple[Array, Optional[Dict[str, Array]]]:
    prev = cache.get("px_cm") if cache else None
    xprev = _token_shift(x, prev)
    sx = xprev - x
    xk = x + sx * p["mu_ck"]
    xr = x + sx * p["mu_cr"]
    k = jnp.square(jax.nn.relu(xk @ p["wk_c"]))
    v = k @ p["wv_c"]
    out = jax.nn.sigmoid((xr @ p["wr_c"]).astype(jnp.float32)).astype(x.dtype) * v
    new_cache = None
    if cache is not None:
        new_cache = dict(cache)
        new_cache["px_cm"] = x[:, -1]
    return out, new_cache


# ---------------------------------------------------------------------------
# Mamba-style selective SSM (Hymba's parallel SSM heads)
# ---------------------------------------------------------------------------


def mamba_mix(cfg: ModelConfig, p, x: Array,
              cache: Optional[Dict[str, Array]],
              ) -> Tuple[Array, Optional[Dict[str, Array]]]:
    B, S, D = x.shape
    di = cfg.n_heads * cfg.head_dim
    N = cfg.ssm.state_size
    R = cfg.ssm.dt_rank
    W = cfg.ssm.conv_width

    xz = x @ p["in_proj"]                                           # [B,S,di]
    # causal depthwise conv
    prev = (cache.get("conv") if cache else None)
    if prev is None:
        prev = jnp.zeros((B, W - 1, di), xz.dtype)
    xc = jnp.concatenate([prev, xz], axis=1)                        # [B,S+W-1,di]
    idx = jnp.arange(S)[:, None] + jnp.arange(W)[None, :]           # [S,W]
    windows = xc[:, idx]                                            # [B,S,W,di]
    xconv = jnp.einsum("bswd,wd->bsd", windows, p["conv_w"])
    xs = jax.nn.silu(xconv)

    proj = xs @ p["x_proj"]                                         # [B,S,R+2N]
    dt_lr, Bmat, Cmat = jnp.split(proj, [R, R + N], axis=-1)
    dt = jax.nn.softplus(dt_lr @ p["dt_proj"] + p["dt_bias"]).astype(jnp.float32)
    A = -jnp.exp(p["a_log"].astype(jnp.float32))                    # [di,N]
    Abar = jnp.exp(dt[..., None] * A)                               # [B,S,di,N]
    Bx = (dt[..., None] * Bmat[:, :, None, :].astype(jnp.float32)
          * xs[..., None].astype(jnp.float32))                      # [B,S,di,N]

    s0 = (cache["ssm"].astype(jnp.float32) if cache
          else jnp.zeros((B, di, N), jnp.float32))

    if S == 1:
        s1 = Abar[:, 0] * s0 + Bx[:, 0]
        ys = jnp.einsum("bdn,bn->bd", s1, Cmat[:, 0].astype(jnp.float32))[:, None]
        state = s1
    else:
        L = _chunk_of(S, 128)
        nch = S // L

        @jax.checkpoint
        def chunk(st, a, b, c):
            # associative scan within chunk: (a, b) composition
            def comb(x1, x2):
                return (x1[0] * x2[0], x2[0] * x1[1] + x2[1])
            aa, bb = lax.associative_scan(comb, (a, b), axis=1)
            states = aa * st[:, None] + bb                          # [B,L,di,N]
            y = jnp.einsum("bldn,bln->bld", states, c.astype(jnp.float32))
            return states[:, -1], y

        def body(st, idx):
            a = lax.dynamic_slice_in_dim(Abar, idx * L, L, axis=1)
            b = lax.dynamic_slice_in_dim(Bx, idx * L, L, axis=1)
            c = lax.dynamic_slice_in_dim(Cmat, idx * L, L, axis=1)
            return chunk(st, a, b, c)

        state, ys = lax.scan(body, s0, jnp.arange(nch))
        ys = ys.transpose(1, 0, 2, 3).reshape(B, S, di)
    y = ys.astype(x.dtype) + xs * p["d_skip"].astype(x.dtype)

    new_cache = None
    if cache is not None:
        new_cache = dict(cache)
        new_cache["ssm"] = state.astype(cache["ssm"].dtype)
        new_cache["conv"] = xc[:, -(W - 1):] if W > 1 else cache["conv"]
    return y, new_cache


# ---------------------------------------------------------------------------
# Block dispatch
# ---------------------------------------------------------------------------

_ZERO_AUX = {"moe_aux": jnp.float32(0.0), "moe_z": jnp.float32(0.0)}


def apply_block(kind: str, cfg: ModelConfig, p: Dict[str, Any], h: Array,
                ctx: BlockCtx, cache: Optional[Dict[str, Array]] = None,
                ) -> Tuple[Array, Optional[Dict[str, Array]], Dict[str, Array]]:
    aux = dict(_ZERO_AUX)
    if kind in ("dense", "moe", "cross"):
        a, new_cache = attention(cfg, p["attn"], norm(cfg, p["ln1"], h), ctx, cache)
        h = h + a
        if kind == "cross":
            xa, xc = cross_attention(cfg, p["xattn"], norm(cfg, p["lnx"], h),
                                     ctx, cache)
            h = h + jnp.tanh(p["xgate"].astype(jnp.float32)).astype(h.dtype) * xa
            if new_cache is not None and xc is not None:
                new_cache = {**new_cache, **{k2: v2 for k2, v2 in xc.items()
                                             if k2 in ("xk", "xv")}}
        hn = norm(cfg, p["ln2"], h)
        if kind == "moe":
            f, moe_aux = moe_ffn(cfg, p["moe"], hn, ctx)
            aux = {k2: aux[k2] + moe_aux[k2] for k2 in aux}
        else:
            f = ffn(cfg, p["ffn"], hn)
        h = h + f
    elif kind == "rwkv":
        t, new_cache = rwkv_time_mix(cfg, p["rwkv"], norm(cfg, p["ln1"], h),
                                     cache, impl=ctx.impl)
        h = h + t
        c, new_cache2 = rwkv_channel_mix(cfg, p["rwkv"], norm(cfg, p["ln2"], h),
                                         new_cache)
        new_cache = new_cache2 if new_cache2 is not None else new_cache
        h = h + c
    elif kind == "hymba":
        hn = norm(cfg, p["ln1"], h)
        a, attn_cache = attention(cfg, p["attn"], hn, ctx, cache)
        s, ssm_cache = mamba_mix(cfg, p["ssm"], hn, cache)
        di = cfg.n_heads * cfg.head_dim

        def _rms(v, scale):
            vf = v.astype(jnp.float32)
            return (vf * lax.rsqrt(jnp.mean(vf * vf, -1, keepdims=True) + 1e-6)
                    * scale).astype(v.dtype)

        fused = 0.5 * (_rms(a, p["norm_attn"]) + _rms(s, p["norm_ssm"]))
        y = jnp.einsum("bshk,hkd->bsd",
                       fused.reshape(*fused.shape[:-1], cfg.n_heads, cfg.head_dim),
                       p["attn"]["wo"])
        h = h + y
        new_cache = None
        if cache is not None:
            new_cache = dict(cache)
            if attn_cache:
                new_cache.update({k2: attn_cache[k2] for k2 in attn_cache
                                  if k2 in ("k", "v", "k_s", "v_s")})
            if ssm_cache:
                new_cache.update({k2: ssm_cache[k2] for k2 in ("ssm", "conv")})
        f = ffn(cfg, p["ffn"], norm(cfg, p["ln2"], h))
        h = h + f
    else:
        raise ValueError(kind)

    # ---- the paper's serial adapter, after the FFN/channel-mix sublayer ----
    h = apply_adapter(p["adapter"], h, activation=cfg.adapter.activation,
                      impl=ctx.impl)
    if ctx.act_spec is not None:
        h = lax.with_sharding_constraint(h, ctx.act_spec)
    return h, new_cache, aux
