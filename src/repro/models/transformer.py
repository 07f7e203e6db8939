"""Generic pattern-scanned transformer with RingAda's static unfreeze boundary.

The layer stack is organized as ``cfg.pattern`` (e.g. ``[(dense,4),(cross,1)]``)
repeated ``cfg.repeats`` times, with parameters stacked ``[R, C, ...]`` and executed
as an outer ``lax.scan`` over repeats and an inner scan over the pattern counts.

RingAda's *scheduled layer unfreezing* enters as the static ``boundary`` argument of
:func:`forward`: repeats ``[0, boundary)`` run inside ``lax.stop_gradient`` in their
own scan, so reverse-mode autodiff emits **no backward pass and saves no residuals**
for the frozen trunk — the exact compute/memory saving the paper's early-stopped
backpropagation provides, realized at the XLA level. (``boundary`` counts *frozen*
repeats from the bottom; unfreeze depth ``d`` maps to ``boundary = R - d``.)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro import scopes
from repro.configs.base import ModelConfig
from repro.models import kvcache
from repro.models.blocks import BlockCtx, apply_block, norm

Array = jax.Array

_ZERO_AUX = lambda: {"moe_aux": jnp.float32(0.0), "moe_z": jnp.float32(0.0)}


def pick_chunk(n: int, cap: int = 512) -> int:
    """Largest divisor of n that is <= cap (query-chunk / scan-chunk size)."""
    best = 1
    for d in range(1, min(n, cap) + 1):
        if n % d == 0:
            best = d
    return best


def n_meta(cfg: ModelConfig) -> int:
    return 128 if any(k == "hymba" for k, _ in cfg.pattern) else 0


# ---------------------------------------------------------------------------
# Stack execution
# ---------------------------------------------------------------------------


def _tree_slice(tree, lo: int, hi: int):
    return jax.tree.map(lambda x: x[lo:hi], tree)


def _run_repeats(cfg: ModelConfig, blocks, h: Array, aux, ctx: BlockCtx,
                 caches=None, pattern=None):
    """Scan over the (sliced) repeats axis of every pattern entry."""
    pattern = pattern or cfg.pattern
    R = jax.tree.leaves(blocks)[0].shape[0]
    if R == 0:
        return h, aux, caches

    has_cache = caches is not None

    def repeat_body(carry, xs):
        hh, ax = carry
        if has_cache:
            entry_params, entry_caches = xs
        else:
            entry_params, entry_caches = xs, [None] * len(pattern)
        new_caches = []
        for (kind, count), ep, ec in zip(pattern, entry_params, entry_caches):
            def block_core(p2, h2, cache2, kind=kind):
                return apply_block(kind, cfg, p2, h2, ctx, cache2)

            if ctx.remat and not has_cache:
                block_core = jax.checkpoint(block_core)

            def inner(c2, xs2, block_core=block_core):
                h2, ax2 = c2
                p2, cache2 = xs2 if has_cache else (xs2, None)
                h3, nc, a = block_core(p2, h2, cache2)
                ax3 = {k: ax2[k] + a[k] for k in ax2}
                return (h3, ax3), nc

            xs_inner = (ep, ec) if has_cache else ep
            (hh, ax), nc = lax.scan(inner, (hh, ax), xs_inner)
            new_caches.append(nc)
        return (hh, ax), tuple(new_caches) if has_cache else None

    xs = (blocks, caches) if has_cache else blocks
    (h, aux), ys = lax.scan(repeat_body, (h, aux), xs)
    return h, aux, (ys if has_cache else None)


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def embed(cfg: ModelConfig, params, tokens: Array, positions: Array) -> Array:
    h = jnp.take(params["embed"]["tok"], tokens, axis=0)
    if not cfg.rope and "pos" in params["embed"]:
        pt = params["embed"]["pos"]
        h = h + jnp.take(pt, jnp.clip(positions, 0, pt.shape[0] - 1), axis=0)
    return h


def head(cfg: ModelConfig, params, h: Array) -> Array:
    h = norm(cfg, params["final_norm"], h)
    logits = h @ params["head"]["w"]
    if cfg.head_out is None and cfg.padded_vocab > cfg.vocab_size:
        # vocab is padded for even sharding; pad logits never win
        pad = jnp.where(jnp.arange(cfg.padded_vocab) < cfg.vocab_size,
                        0.0, -1e30).astype(logits.dtype)
        logits = logits + pad
    return logits


# ---------------------------------------------------------------------------
# Encoder (seamless): non-causal dense stack over pre-embedded frames
# ---------------------------------------------------------------------------


def encode(cfg: ModelConfig, params, frames: Array, *, impl: str = "jnp",
           remat: bool = False, act_spec=None) -> Array:
    B, T, D = frames.shape
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    ctx = BlockCtx(cfg=cfg, mode="seq", positions=pos, causal=False, impl=impl,
                   q_chunk=pick_chunk(T), remat=remat, act_spec=act_spec)
    h, _, _ = _run_repeats(cfg, params["encoder"]["blocks"], frames, _ZERO_AUX(),
                           ctx, pattern=(("dense", 1),))
    return norm(cfg, params["encoder"]["final_norm"], h)


# ---------------------------------------------------------------------------
# Forward (train / eval over a full sequence)
# ---------------------------------------------------------------------------


def forward(params, tokens: Array, cfg: ModelConfig, *,
            memory: Optional[Array] = None,
            boundary: int = 0,
            impl: str = "jnp",
            remat: bool = False,
            act_spec=None,
            moe_groups: int = 1,
            hot_adapters: Optional[Tuple] = None,
            head_params: Optional[Dict[str, Array]] = None,
            ) -> Tuple[Array, Dict[str, Array]]:
    """Returns (logits [B, S, V], aux). ``boundary`` = frozen repeats (static).

    ``memory``: VLM patch embeddings / audio frames (enc-dec encodes them first).

    ``hot_adapters`` / ``head_params``: when training, the differentiated leaves
    are passed *separately* (already sliced ``[boundary:]``) rather than merged
    into ``params`` — slicing a concat of (frozen, hot) rows would make the
    frozen scan appear differentiable to JAX (concat JVP materializes zero
    tangents) and re-linearize the whole trunk, destroying the early-stop win.
    """
    B, S = tokens.shape
    nm = n_meta(cfg)
    if cfg.enc_dec:
        assert memory is not None, "enc-dec needs frontend frames"
        memory = encode(cfg, params, memory, impl=impl, remat=remat,
                        act_spec=act_spec)

    pos = jnp.broadcast_to(jnp.arange(nm + S, dtype=jnp.int32)[None], (B, nm + S))
    ctx = BlockCtx(cfg=cfg, mode="seq", positions=pos, causal=True, memory=memory,
                   impl=impl, q_chunk=pick_chunk(nm + S), remat=remat,
                   act_spec=act_spec, moe_groups=moe_groups)

    aux = _ZERO_AUX()
    blocks = params["blocks"]
    with jax.named_scope(scopes.TRUNK):
        h = embed(cfg, params, tokens, pos[:, nm:] if nm else pos)
        if nm:
            meta = jnp.broadcast_to(params["meta"][None].astype(h.dtype),
                                    (B, nm, cfg.d_model))
            h = jnp.concatenate([meta, h], axis=1)
        if boundary > 0:
            frozen = tuple(_tree_slice(e, 0, boundary) for e in blocks)
            frozen = lax.stop_gradient(frozen)
            h, aux, _ = _run_repeats(cfg, frozen, h, aux,
                                     dataclasses.replace(ctx, primal_only=True))
            # === RingAda early-stop point: no gradients flow below this line ===
            h = lax.stop_gradient(h)
            aux = jax.tree.map(lax.stop_gradient, aux)
    if boundary < cfg.repeats:
        with jax.named_scope(scopes.HOT):
            hot = tuple(_tree_slice(e, boundary, cfg.repeats) for e in blocks)
            if hot_adapters is not None:
                hot = tuple({**e, "adapter": ha}
                            for e, ha in zip(hot, hot_adapters))
            h, aux, _ = _run_repeats(cfg, hot, h, aux, ctx)

    if nm:
        h = h[:, nm:]
    hp = {**params, "head": head_params} if head_params is not None else params
    with jax.named_scope(scopes.HEAD):
        logits = head(cfg, hp, h)
    return logits, aux


# ---------------------------------------------------------------------------
# Serving: prefill + single-token decode
# ---------------------------------------------------------------------------


def prefill(params, tokens: Array, cfg: ModelConfig, *,
            memory: Optional[Array] = None, seq_len: Optional[int] = None,
            impl: str = "jnp", act_spec=None, moe_groups: int = 1,
            ) -> Tuple[Array, Dict[str, Any]]:
    """Run the prompt, return (last-token logits [B, V], filled cache).

    ``seq_len``: total decode horizon the cache must support (>= prompt length).
    """
    B, S = tokens.shape
    nm = n_meta(cfg)
    seq_len = seq_len or (nm + S)
    if cfg.enc_dec:
        memory = encode(cfg, params, memory, impl=impl)
    mem_len = memory.shape[1] if memory is not None else 0

    cache = kvcache.init_cache(cfg, B, seq_len, mem_len=mem_len)
    pos = jnp.broadcast_to(jnp.arange(nm + S, dtype=jnp.int32)[None], (B, nm + S))
    h = embed(cfg, params, tokens, pos[:, nm:] if nm else pos)
    if nm:
        meta = jnp.broadcast_to(params["meta"][None].astype(h.dtype),
                                (B, nm, cfg.d_model))
        h = jnp.concatenate([meta, h], axis=1)

    # deterministic gather-fill slots: for each cache slot, the last prompt
    # position that lands in it (ring buffer), or -1 if unwritten.
    ck = kvcache.cache_len(cfg, seq_len)
    ns = kvcache.n_sink(cfg)
    Sp = nm + S
    if cfg.sliding_window is None or ck >= seq_len:
        assert Sp <= ck, (f"prompt ({Sp} incl. meta) exceeds cache horizon "
                          f"({ck}); raise seq_len")
    slots = jnp.arange(ck, dtype=jnp.int32)
    if cfg.sliding_window is not None and ck < seq_len:
        w = ck - ns
        cand = jnp.where(slots < ns, slots,
                         slots + w * (jnp.maximum(Sp - 1 - slots, 0) // w))
    else:
        cand = slots
    fill_pos = jnp.where(cand < Sp, cand, -1)                      # [ck]
    cache["pos"] = jnp.broadcast_to(fill_pos[None], (B, ck))
    cache["next"] = jnp.full((B,), Sp, jnp.int32)

    ctx = BlockCtx(cfg=cfg, mode="prefill", positions=pos, causal=True,
                   memory=memory, impl=impl, q_chunk=pick_chunk(Sp),
                   act_spec=act_spec, moe_groups=moe_groups,
                   cache_positions=jnp.broadcast_to(fill_pos[None], (B, ck)),
                   write_slots=None)
    # prefill uses gather-fill: attention sees the full kk/vv it just computed and
    # the cache is written from ``fill_pos`` gathers (no duplicate-scatter).
    ctx.write_slots = jnp.where(fill_pos < 0, 0, fill_pos)[None].repeat(B, 0)

    aux = _ZERO_AUX()
    h, aux, new_layer_caches = _run_prefill(cfg, params["blocks"], h, aux, ctx,
                                            cache["layers"], fill_pos)
    cache["layers"] = new_layer_caches
    logits = head(cfg, params, h[:, -1:])[:, 0]
    return logits, cache


def _run_prefill(cfg, blocks, h, aux, ctx: BlockCtx, caches, fill_pos):
    """Prefill = seq-mode forward + cache construction via gathers."""
    # Run blocks in "prefill" mode: attention computes over its freshly-projected
    # kk/vv, then gathers rows at ``fill_pos`` into the cache (see blocks.attention
    # handling below via mode). We emulate by running each layer with cache and
    # mode="prefill"; blocks check ctx.mode.
    ctx2 = dataclasses.replace(ctx, mode="prefill")
    return _run_repeats(cfg, blocks, h, aux, ctx2, caches=caches)


def decode_step(params, token: Array, cache: Dict[str, Any], cfg: ModelConfig,
                *, impl: str = "jnp", act_spec=None
                ) -> Tuple[Array, Dict[str, Any]]:
    """One decode step. token [B, 1] int32. Returns (logits [B, V], new cache)."""
    B = token.shape[0]
    pos = cache["next"][:, None]                                    # [B, 1]
    h = embed(cfg, params, token, pos)

    ck = cache["pos"].shape[1]
    seq_len_equiv = ck if cfg.sliding_window is None else cfg.max_seq_len
    slot = kvcache.write_slot(cfg, pos, seq_len_equiv)
    slot = jnp.minimum(slot, ck - 1)
    new_pos_arr = cache["pos"].at[jnp.arange(B)[:, None], slot].set(pos)

    ctx = BlockCtx(cfg=cfg, mode="step", positions=pos, causal=True,
                   memory=None, impl=impl, q_chunk=1, act_spec=act_spec,
                   cache_positions=new_pos_arr, write_slots=slot)
    aux = _ZERO_AUX()
    h, aux, new_layer_caches = _run_repeats(cfg, params["blocks"], h, aux, ctx,
                                            caches=cache["layers"])
    logits = head(cfg, params, h)[:, 0]
    new_cache = {"layers": new_layer_caches, "pos": new_pos_arr,
                 "next": cache["next"] + 1}
    return logits, new_cache
