"""RingExecutor: the fused end-to-end ring training step.

One donated, jitted executable per unfreeze boundary runs a FULL RingAda round
— all S owner-iterations (forward, early-stopped backward, stage-masked AdamW
on the adapters, replicated AdamW on the head) — entirely on device:

  * the owner rotation is a ``lax.scan`` over owners *inside* the executable;
    the owner-dependent hops use ``pipeline.ring_round_local``'s dynamic
    permutes so owner can be traced (the reference ``RingTrainer`` instead
    compiles one executable per (owner, boundary) pair: S x boundaries),
  * the optimizer is ``optim.adamw.tree_update`` with a stage mask
    ``stage >= F`` — frozen stages' adapters AND their Adam moments are
    bit-identical before and after the round,
  * params + optimizer moments are donated (``donate_argnums``), so the round
    updates in place instead of holding two copies live,
  * nothing syncs to the host: ``round()`` returns device arrays; callers
    ``float()`` them once per logging interval (async dispatch).

Packed-conveyor Phase A (``packed=True``, the default): instead of re-running
a ``M + F - 1``-tick frozen-trunk pipeline inside every owner-iteration of the
scan, the executor runs ``pipeline.ring_phase_a_packed``'s single
``S*M + F - 1``-tick conveyor ONCE per round before the scan and feeds the
owner iterations from the resulting ``[S, M, ...]`` boundary stack — the
frozen trunk is round-constant, so the streams pack back-to-back and the
round saves ``(S-1)*(F-1)`` fill/drain ticks.  ``packed=False`` keeps the
per-owner scheme (A/B benchmarked in ``benchmarks/pipeline_bench.py``).

Frozen-trunk activation cache (Phase-A skip, ``core/actcache.py``): with a
``cache_capacity`` and slot-keyed batches, the executor builds up to three
executables per boundary —

  * ``direct``  — the PR-1 fused round (tokens in, no capture),
  * ``capture`` — same round, but each owner-iteration's stage-``F`` boundary
    activations are additionally emitted and written into the cache's donated
    device ring buffer (first visit of a ``(slot, boundary)`` key),
  * ``cached``  — takes ``(cache_buffer, row)`` instead of tokens and launches
    straight into Phase B: no embed, no ``all_gather``, no frozen-trunk ticks.
    The row and the owner are traced, so one executable serves every slot and
    owner; the gather of the cached activations happens on device.

``cache_dtype`` ({'native', 'f32', 'bf16', 'int8'}) compresses the cache's
entries — bf16 halves, int8 (per-row scales in a sidecar buffer) quarters the
bytes per entry, 2-4x more slots per byte of cache budget; the cached
executable dequantizes on device right after the row gather.

Boundary drops invalidate the whole cache (the unfreeze schedule is monotone
top-down — enforced here and in ``core/unfreeze.py``).  Batches whose shapes
don't fit the allocated buffer, or rounds without a slot key (streaming data),
fall back to ``direct``.

Heterogeneous rings (``spans=``): the executor runs any contiguous span
layout — ``partition.assign_layers`` output for speed-weighted heterogeneous
meshes (the paper's 4:5:2:3), or the balanced default.  The unfreeze boundary
aligns DOWN to span edges, the cache binds to the layout
(``ActivationCache.set_layout`` flushes it on ``repartition``), and
``measured_tick_ledger`` exposes the scan lengths actually traced per
``(boundary, mode)`` executable for the simulator-vs-executor differential
tests (tests/test_partition_exec.py).

Multi-tenant personalization (``tenants=T > 1``): one frozen trunk, T adapter
sets per ring.  Adapters/moments gain an interior tenant axis
([S, T, max_span, ...]; head [T, ...]), the packed conveyor chains all T·S·M
tenant-owner microbatches of a round into one ``T·S·M + F - 1``-tick Phase-A
pass (the trunk is frozen and bit-identical across tenants, and per-tick
shapes stay exactly single-tenant, so each microbatch's op sequence is
bit-identical to a solo run), Phase B + AdamW scan over the tenant axis with
single-tenant shapes inside, and the activation cache
partitions per tenant under ``(tenant, slot, boundary)`` keys with per-tenant
invalidation (``import_adapters`` flushes one tenant without touching its
neighbors).  Per tenant, a joint T-tenant session matches T independent
single-tenant sessions — asserted by tests/test_tenants.py.

Numerics match ``RingTrainer`` exactly (same ``adamw.leaf_update`` math,
constant lr, no bias correction) — asserted by tests/test_executor.py; the
cached path matches the uncached fused path — asserted by
tests/test_actcache.py.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import scopes
from repro.configs.base import ModelConfig, TrainConfig
from repro.core import actcache
from repro.core import pipeline as pl
from repro.core.actcache import ActivationCache
from repro.core.partition import (DeviceProfile, Span, align_boundary,
                                  frozen_stage_count, spans_from_profiles)
from repro.core.unfreeze import UnfreezeSchedule, depth_to_boundary
from repro.optim import adamw

Array = jax.Array

FUSED_MODES = ("direct", "capture", "cached")


def scalarize(v: Any) -> Any:
    """Device metric value -> host scalar / list (non-arrays pass through).

    The ONE materialization rule for async metrics — shared by
    ``RingExecutor.materialize_metrics`` and ``repro.api.metrics``.
    """
    if isinstance(v, jax.Array):
        return float(v) if v.ndim == 0 else [float(x) for x in v]
    return v


def ring_opt_init(stage_blocks: Dict[str, Any], shared: Dict[str, Any]
                  ) -> Dict[str, Any]:
    """Ring optimizer state: adapter moments stage-stacked [S, lps, ...]
    (sharded with the adapters — optimizer state never crosses the ring, like
    the paper), head moments replicated."""
    m_ad, v_ad = adamw.init_moments(stage_blocks["adapter"])
    m_hd, v_hd = adamw.init_moments(shared["head"])
    return {"m": {"adapter": m_ad, "head": m_hd},
            "v": {"adapter": v_ad, "head": v_hd},
            "count": jnp.zeros((), jnp.int32)}


def ring_opt_specs() -> Dict[str, Any]:
    """PartitionSpec tree matching ``ring_opt_init``'s structure."""
    return {"m": {"adapter": P("stage"), "head": P()},
            "v": {"adapter": P("stage"), "head": P()},
            "count": P()}


def make_fused_round(cfg: ModelConfig, tc: TrainConfig, mesh: Mesh, *,
                     n_stages: int, boundary: int, n_micro: int,
                     on_trace=None, mode: str = "direct",
                     packed: bool = True, cache_dtype: str = "native",
                     cache_src_dtype: Any = None,
                     spans: Optional[Sequence[Span]] = None,
                     tick_record=None, tenants: int = 1):
    """Build the fused round in one of three modes:

      direct :  fn(stage_blocks, shared, opt_state, tokens, labels)
                  -> (stage_blocks, shared, opt_state, (losses[S], mean))
      capture:  same signature, plus a trailing ``h_cap`` output
                ([S_stage, S_owner, M, mb, seq, D], sharded on 'stage'):
                every owner-iteration's Phase-A output, ready for the cache.
      cached :  fn(stage_blocks, shared, opt_state, cache_buf, row, labels)
                  -> (stage_blocks, shared, opt_state, (losses[S], mean))
                where ``cache_buf`` is the actcache ring buffer
                ([capacity, S_stage, S_owner, M, mb, seq, D], sharded
                P(None, 'stage')) and ``row`` a traced i32 row index.
                With ``cache_dtype='int8'`` the signature gains a
                ``cache_scales`` sidecar after ``cache_buf``; entries are
                dequantized on device right after the row gather
                (``actcache.dequantize`` with the static ``cache_dtype``).
                Phase A (embed + all_gather + frozen-trunk ticks) is absent
                from the executable entirely.

    ``packed`` (direct/capture only) selects the Phase-A scheme: True runs
    ``pipeline.ring_phase_a_packed``'s single ``S*M + F - 1``-tick conveyor
    once per round before the owner scan (the frozen trunk is round-constant,
    so all S owners' streams pack back-to-back, saving ``(S-1)*(F-1)``
    fill/drain ticks); False keeps the per-owner ``M + F - 1``-tick pipeline
    inside the scan (the PR-2 scheme, kept for A/B benchmarking).  Both are
    numerically the same per microbatch.  At ``F <= 1`` the saving is zero
    while the conveyor would still hold the whole ``[S*M, ...]`` stream live,
    so ``packed`` silently falls back to the scan there (measured ~9%
    slower otherwise on the 2-device mesh — see BENCH_ring_2dev.json).

    ``spans`` selects the stage layout ([(begin, end)] per stage, e.g. the
    paper's 4:5:2:3 from ``partition.assign_layers``); None is the balanced
    split.  ``boundary`` must be span-aligned.  ``tick_record(phase, ticks)``
    (if given) is called at trace time with each tick scan's length — the
    measured ledger tests/test_partition_exec.py pins against
    ``pipeline.pipeline_tick_counts``.

    Static per build: (boundary, mode, packed, cache_dtype, spans, tenants).
    ``on_trace`` (if given) is called each time the function body is traced
    — i.e. once per XLA compilation — which is how tests count executables.
    Wrap the result in ``jax.jit(..., donate_argnums=(0, 1, 2))``
    (RingExecutor does; the cache buffers are never donated — they outlive
    the round).

    Multi-tenant (``tenants=T > 1``): one frozen trunk, T adapter sets.
    Input trees gain one interior tenant axis — adapter leaves
    ``[S, T, max_span, ...]`` (still sharded P('stage')), head/opt-head
    ``[T, ...]`` (replicated), tokens/labels ``[S, T, M, mb, seq]`` — so
    every PartitionSpec is IDENTICAL to T=1.  Phase A runs once on the
    shared trunk with all tenants chained onto the conveyor's time axis
    (``ring_phase_a_packed(n_tenants=T)``); Phase B runs per tenant via a
    ``lax.scan`` over the stacked adapters (single-tenant shapes inside),
    and the masked AdamW update is elementwise on the stacked moments —
    both bit-equivalent to T independent single-tenant updates (the
    scalar stage mask broadcasts).  The metrics tuple gains a trailing
    ``tenant_losses [T]``; capture emits ``[T, S_stage, S_owner, M, ...]``
    (one cache entry per tenant) and cached mode takes a ``rows [T]``
    vector instead of a scalar row.
    """
    assert mode in FUSED_MODES, mode
    assert tenants >= 1, tenants
    T = tenants
    S = n_stages
    spans = pl.resolve_spans(cfg.repeats, S, spans)
    F = frozen_stage_count(spans, boundary)
    rec = tick_record or (lambda phase, t: None)
    phase_a = pl.ring_phase_a(cfg, n_stages=S, boundary=boundary,
                              n_micro=n_micro, spans=spans,
                              record=lambda t: rec("phase_a", t))
    phase_a_packed = pl.ring_phase_a_packed(
        cfg, n_stages=S, boundary=boundary, n_micro=n_micro, spans=spans,
        record=lambda t: rec("phase_a_packed", t), n_tenants=T)
    phase_b = pl.ring_phase_b(cfg, n_stages=S, boundary=boundary,
                              n_micro=n_micro, spans=spans,
                              record=lambda t: rec("phase_b", t))
    lr = jnp.float32(tc.learning_rate)
    # what Phase B received at capture time: compressed entries dequantize
    # back to exactly this dtype (the captured activations' own dtype when
    # the executor knows it, else the model compute dtype).
    compute_dtype = jnp.dtype(cache_src_dtype if cache_src_dtype is not None
                              else cfg.dtype)

    def run_round(stage_blocks, shared, opt_state, get_h_B, my_labels):
        """Owner scan + stage-masked optimizer, Phase-A source abstracted:
        ``get_h_B(owner, adapters)`` -> the stage-F injects [M, mb, seq, D]."""
        hot = (lax.axis_index("stage") >= F).astype(jnp.float32)
        my_blocks = jax.tree.map(lambda x: x[0], stage_blocks)
        backbone = {k: v for k, v in my_blocks.items() if k != "adapter"}
        shared_rest = {k: v for k, v in shared.items() if k != "head"}
        unstage = lambda t: jax.tree.map(lambda x: x[0], t)
        restage = lambda t: jax.tree.map(lambda x: x[None], t)

        def owner_iter(carry, owner):
            ad, head, m_ad, v_ad, m_hd, v_hd = carry
            h_B = get_h_B(owner, ad)

            def local_loss(ad_, head_):
                return phase_b(owner, {**backbone, "adapter": ad_},
                               {**shared_rest, "head": head_}, h_B, my_labels)

            l_loc, (g_ad, g_hd) = jax.value_and_grad(
                local_loss, argnums=(0, 1))(ad, head)
            with jax.named_scope(scopes.OPTIMIZER):
                # head grads live only on the owner stage; psum replicates
                # them (same semantics as differentiating a replicated P()
                # input).
                g_hd = jax.tree.map(lambda g: lax.psum(g, "stage"), g_hd)
                ad2, m_ad2, v_ad2 = adamw.tree_update(
                    g_ad, m_ad, v_ad, ad, tc, lr=lr, mask=hot)
                head2, m_hd2, v_hd2 = adamw.tree_update(
                    g_hd, m_hd, v_hd, head, tc, lr=lr)
            return (ad2, head2, m_ad2, v_ad2, m_hd2, v_hd2), (l_loc, h_B)

        init = (my_blocks["adapter"], shared["head"],
                unstage(opt_state["m"]["adapter"]), unstage(opt_state["v"]["adapter"]),
                opt_state["m"]["head"], opt_state["v"]["head"])
        (ad, head, m_ad, v_ad, m_hd, v_hd), (local_losses, h_caps) = lax.scan(
            owner_iter, init, jnp.arange(S))
        # each iteration's loss lives only on its owner stage; one vector psum
        # per round replicates all S of them at once.
        losses = lax.psum(local_losses, "stage")
        mean_loss = jnp.mean(losses)

        new_blocks = {**stage_blocks, "adapter": restage(ad)}
        new_shared = {**shared, "head": head}
        new_opt = {"m": {"adapter": restage(m_ad), "head": m_hd},
                   "v": {"adapter": restage(v_ad), "head": v_hd},
                   "count": opt_state["count"] + S}
        return new_blocks, new_shared, new_opt, (losses, mean_loss), h_caps

    def run_round_mt(stage_blocks, shared, opt_state, get_h_B, my_labels):
        """Multi-tenant owner scan: Phase B scans over the tenant axis, the
        masked AdamW update runs elementwise on the tenant-stacked moments.
        ``get_h_B(owner, adapters)`` -> [T, M, mb, seq, D]; ``my_labels``
        [T, M, mb, seq]; adapter leaves carry [T, max_span, ...] inside the
        scan, head leaves [T, ...].  Per tenant this is exactly
        ``run_round``'s math on exactly single-tenant shapes, so joint
        training equals T independent sessions bit-for-bit."""
        hot = (lax.axis_index("stage") >= F).astype(jnp.float32)
        my_blocks = jax.tree.map(lambda x: x[0], stage_blocks)
        backbone = {k: v for k, v in my_blocks.items() if k != "adapter"}
        shared_rest = {k: v for k, v in shared.items() if k != "head"}
        unstage = lambda t: jax.tree.map(lambda x: x[0], t)
        restage = lambda t: jax.tree.map(lambda x: x[None], t)

        def owner_iter(carry, owner):
            ad, head, m_ad, v_ad, m_hd, v_hd = carry
            h_B = get_h_B(owner, ad)                 # [T, M, mb, seq, D]

            # Per-tenant Phase B over the stacked adapters: a lax.scan over
            # the tenant axis, NOT a vmap — inside the scan every tensor has
            # exactly the single-tenant shapes, so each tenant's grads (and
            # thus its Adam trajectory) are bit-identical to an independent
            # single-tenant session.  A vmap batches the kernels ([T, ...]
            # shapes), which reassociates reductions at the ulp level — and
            # the first Adam steps amplify ulp-level grad noise to O(lr)
            # sign flips, blowing the 1e-5/1e-3 differential pins.
            def per_tenant(_, args):
                ad_t, head_t, h_t, lab_t = args

                def local_loss(ad_, head_):
                    return phase_b(owner, {**backbone, "adapter": ad_},
                                   {**shared_rest, "head": head_}, h_t, lab_t)

                return None, jax.value_and_grad(
                    local_loss, argnums=(0, 1))(ad_t, head_t)

            _, (l_loc, (g_ad, g_hd)) = lax.scan(
                per_tenant, None, (ad, head, h_B, my_labels))  # l_loc [T]
            with jax.named_scope(scopes.OPTIMIZER):
                g_hd = jax.tree.map(lambda g: lax.psum(g, "stage"), g_hd)
                # stacked trees, same elementwise update: the scalar ``hot``
                # mask broadcasts over the leading tenant axis.
                ad2, m_ad2, v_ad2 = adamw.tree_update(
                    g_ad, m_ad, v_ad, ad, tc, lr=lr, mask=hot)
                head2, m_hd2, v_hd2 = adamw.tree_update(
                    g_hd, m_hd, v_hd, head, tc, lr=lr)
            return (ad2, head2, m_ad2, v_ad2, m_hd2, v_hd2), (l_loc, h_B)

        init = (my_blocks["adapter"], shared["head"],
                unstage(opt_state["m"]["adapter"]), unstage(opt_state["v"]["adapter"]),
                opt_state["m"]["head"], opt_state["v"]["head"])
        (ad, head, m_ad, v_ad, m_hd, v_hd), (local_losses, h_caps) = lax.scan(
            owner_iter, init, jnp.arange(S))
        losses_to = lax.psum(local_losses, "stage")  # [S_owner, T]
        mean_loss = jnp.mean(losses_to)
        tenant_losses = losses_to.mean(axis=0)       # [T]
        losses = losses_to.mean(axis=1)              # [S] per-owner, T=1 shape

        new_blocks = {**stage_blocks, "adapter": restage(ad)}
        new_shared = {**shared, "head": head}
        new_opt = {"m": {"adapter": restage(m_ad), "head": m_hd},
                   "v": {"adapter": restage(v_ad), "head": v_hd},
                   "count": opt_state["count"] + S}
        return (new_blocks, new_shared, new_opt,
                (losses, mean_loss, tenant_losses), h_caps)

    run = run_round if T == 1 else run_round_mt
    met_spec = (P(), P()) if T == 1 else (P(), P(), P())

    if mode in ("direct", "capture"):

        def fused(stage_blocks, shared, opt_state, tokens, labels):
            if on_trace is not None:
                on_trace()
            my_tokens, my_labels = tokens[0], labels[0]
            my_blocks = jax.tree.map(lambda x: x[0], stage_blocks)
            backbone = {k: v for k, v in my_blocks.items() if k != "adapter"}
            shared_rest = {k: v for k, v in shared.items() if k != "head"}

            # Embeddings are round-constant (outside the trainable set): embed +
            # gather once, not once per owner-iteration.
            seq = my_tokens.shape[-1]
            mb = my_tokens.shape[-2]
            pos = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32)[None],
                                   (mb, seq))
            if T == 1:
                emb_g = pl.gather_embeddings(cfg, shared_rest, my_tokens, pos)
            else:
                # my_tokens [T, M, mb, seq]: embed all tenants' microbatches
                # in one vmap, then restore the tenant axis.
                tok_flat = my_tokens.reshape((T * n_micro,)
                                             + my_tokens.shape[2:])
                e = pl.gather_embeddings(cfg, shared_rest, tok_flat, pos)
                emb_g = e.reshape((S, T, n_micro) + e.shape[2:])

            # The shared frozen trunk: Phase A reads only frozen adapter
            # rows, which are bit-identical across tenants (shared init +
            # stage mask), so any tenant's slice works — use tenant 0.
            trunk_ad = (my_blocks["adapter"] if T == 1 else
                        jax.tree.map(lambda x: x[0], my_blocks["adapter"]))

            if packed and F >= 2:
                # One continuous conveyor over ALL owners' frozen-trunk
                # streams, run before the scan.  Phase A only reads the
                # frozen stages' blocks, and the stage-masked optimizer keeps
                # those bit-identical across owner-iterations, so the
                # round-start adapters give exactly what each iteration's
                # carried adapters would have.  [S, M, ...] / [S, T, M, ...].
                h_B_all = phase_a_packed(
                    {**backbone, "adapter": trunk_ad}, emb_g)

                def get_h_B(owner, ad):
                    return lax.dynamic_index_in_dim(h_B_all, owner, 0,
                                                    keepdims=False)
            elif T == 1:

                def get_h_B(owner, ad):
                    return phase_a(owner, {**backbone, "adapter": ad}, emb_g)
            else:

                def get_h_B(owner, ad):
                    # Per-tenant Phase A as a lax.scan (NOT a vmap): inside
                    # the scan every tensor has exact single-tenant shapes,
                    # keeping each tenant's forward bit-identical to an
                    # independent session (see run_round_mt's Phase-B note).
                    trunk = {**backbone,
                             "adapter": jax.tree.map(lambda x: x[0], ad)}

                    def per_tenant(_, e_t):
                        return None, phase_a(owner, trunk, e_t)

                    _, h = lax.scan(per_tenant, None,
                                    jnp.swapaxes(emb_g, 0, 1))
                    return h                             # [T, M, mb, seq, D]

            blocks2, shared2, opt2, metrics, h_caps = run(
                stage_blocks, shared, opt_state, get_h_B, my_labels)
            if mode == "capture":
                # packed capture writes the whole owner stack in one pass —
                # h_caps is the scan-stacked copy of h_B_all either way.
                if T == 1:
                    return blocks2, shared2, opt2, metrics, h_caps[None]
                # [S_owner, T, M, ...] -> [T, S_stage=1, S_owner, M, ...]:
                # one buffer entry per tenant, each the T=1 entry shape.
                return (blocks2, shared2, opt2, metrics,
                        jnp.swapaxes(h_caps, 0, 1)[:, None])
            return blocks2, shared2, opt2, metrics

        opt_spec = ring_opt_specs()
        out = (P("stage"), P(), opt_spec, met_spec)
        if mode == "capture":
            out = out + ((P("stage"),) if T == 1 else (P(None, "stage"),))
        return jax.shard_map(
            fused, mesh=mesh,
            in_specs=(P("stage"), P(), opt_spec, P("stage"), P("stage")),
            out_specs=out)

    # mode == "cached": Phase A replaced by an on-device gather from the ring
    # buffer — the executable never sees tokens or the embedding table.
    # Compressed entries are dequantized right after the row gather, inside
    # this executable (static ``cache_dtype``), then fed to Phase B in the
    # model's compute dtype — a hit costs zero host<->device traffic at any
    # storage precision.
    def cached_body(stage_blocks, shared, opt_state, h_slot, labels):
        my_labels = labels[0]

        # T=1: h_slot [S_owner, M, ...]; T>1: [T, S_owner, M, ...] — the
        # owner index sits after the tenant axis.
        def get_h_B(owner, ad):
            return lax.dynamic_index_in_dim(h_slot, owner, 0 if T == 1 else 1,
                                            keepdims=False)

        blocks2, shared2, opt2, metrics, _ = run(
            stage_blocks, shared, opt_state, get_h_B, my_labels)
        return blocks2, shared2, opt2, metrics

    def _row(buf, row):
        # [cap, S_stage=1(local), S_owner, ...] -> this stage's row(s).
        # T=1: scalar row -> [S_owner, ...]; T>1: rows [T] -> a gather
        # [T, S_owner, ...] (one buffer row per tenant).
        if T == 1:
            return lax.dynamic_index_in_dim(buf[:, 0], row, 0, keepdims=False)
        return buf[:, 0][row]

    if cache_dtype == "int8":

        def fused_cached_q(stage_blocks, shared, opt_state, cache_buf,
                           cache_scales, row, labels):
            if on_trace is not None:
                on_trace()
            h_slot = actcache.dequantize(
                _row(cache_buf, row), _row(cache_scales, row), "int8",
                compute_dtype)
            return cached_body(stage_blocks, shared, opt_state, h_slot,
                               labels)

        opt_spec = ring_opt_specs()
        return jax.shard_map(
            fused_cached_q, mesh=mesh,
            in_specs=(P("stage"), P(), opt_spec, P(None, "stage"),
                      P(None, "stage"), P(), P("stage")),
            out_specs=(P("stage"), P(), opt_spec, met_spec))

    def fused_cached(stage_blocks, shared, opt_state, cache_buf, row, labels):
        if on_trace is not None:
            on_trace()
        h_slot = actcache.dequantize(_row(cache_buf, row), None, cache_dtype,
                                     compute_dtype)
        return cached_body(stage_blocks, shared, opt_state, h_slot, labels)

    opt_spec = ring_opt_specs()
    return jax.shard_map(
        fused_cached, mesh=mesh,
        in_specs=(P("stage"), P(), opt_spec, P(None, "stage"), P(),
                  P("stage")),
        out_specs=(P("stage"), P(), opt_spec, met_spec))


class RingExecutor:
    """Collaborative fine-tuning over a ring of ``n_stages`` devices — fused.

    Drop-in upgrade of ``core/ring.py``'s ``RingTrainer``: same constructor,
    same ``round(tokens, labels)`` / ``export_params()`` surface, but each
    round is ONE donated executable instead of S dispatches + a host-side
    optimizer loop, and ``round()`` never blocks on the host (metrics are
    device arrays; see ``materialize_metrics``).

    Weights and Adam moments live where the round reads them: block stacks
    (and adapter moments) on their stage, shared leaves (and head moments)
    replicated — written straight into that placement, never staged on one
    device.  ``params=None`` builds the seeded weights from ``tc.seed`` inside
    one sharded jit (``pipeline.init_stage_stack``), so no device ever holds
    the canonical tree; a given canonical tree is stacked into place
    (``pipeline.place_stage_stack``) and not kept.

    With ``cache_capacity > 0``, pass ``slot=<stable batch-slot id>`` to
    ``round``: steady-state revisits of a ``(slot, boundary)`` key skip
    Phase A entirely (see module docstring).  ``slot=None`` (or capacity 0)
    preserves the PR-1 behavior exactly.

    The unfreeze boundary is evaluated once per round (at the round's first
    step).  When ``tc.unfreeze_interval`` is a multiple of ``n_stages`` this is
    identical to the reference trainer's per-iteration evaluation; otherwise a
    mid-round bump is deferred to the next round boundary.
    """

    def __init__(self, cfg: ModelConfig, tc: TrainConfig, mesh: Mesh,
                 params: Optional[Dict[str, Any]], n_stages: int,
                 n_micro: int, *,
                 donate: bool = True, cache_capacity: int = 0,
                 schedule: Optional[Any] = None, packed: bool = True,
                 cache_dtype: str = "native",
                 spans: Optional[Sequence[Span]] = None,
                 tenants: int = 1):
        assert len(cfg.pattern) == 1, "ring executor needs a uniform pattern"
        assert tenants >= 1, tenants
        self.cfg, self.tc, self.mesh = cfg, tc, mesh
        self.S, self.M = n_stages, n_micro
        self.T = tenants
        self.packed = packed
        self.cache_dtype = cache_dtype
        # ``spans`` makes heterogeneous (uneven, assign_layers-produced)
        # stage layouts first-class; None is the balanced split — identical
        # to the historical L/S-per-stage layout when R divides evenly.
        self.spans = pl.resolve_spans(cfg.repeats, n_stages, spans)
        # lps only exists for uniform layouts (back-compat for benches/tests
        # that reason in blocks-per-stage); ragged layouts use self.spans.
        self.lps = (cfg.repeats // n_stages
                    if not pl.is_ragged(self.spans) else None)
        if params is None:
            self.stage_blocks, self.shared = pl.init_stage_stack(
                cfg, mesh, jax.random.key(tc.seed), spans=self.spans)
        else:
            self.stage_blocks, self.shared = pl.place_stage_stack(
                params, cfg, mesh, spans=self.spans)
        if tenants > 1:
            # One frozen trunk, T adapter sets: adapters gain an interior
            # tenant axis [S, T, max_span, ...] (stage axis stays leading so
            # the P('stage') specs are unchanged); the head is per-tenant
            # [T, ...].  All tenants start from the same init — the shared
            # Phase-A trunk relies on frozen rows staying bit-identical.
            self.stage_blocks = {
                **self.stage_blocks,
                "adapter": adamw.tenant_stack(
                    self.stage_blocks["adapter"], tenants, axis=1)}
            self.shared = {
                **self.shared,
                "head": adamw.tenant_stack(self.shared["head"], tenants)}
        opt_sharding = jax.tree.map(lambda spec: NamedSharding(mesh, spec),
                                    ring_opt_specs())
        self.opt_state = jax.jit(ring_opt_init, out_shardings=opt_sharding)(
            self.stage_blocks, self.shared)
        # per-tenant cache accounting (satellite of the partitioned cache:
        # a tenant's invalidation must not move its neighbors' hit-rates)
        self.tenant_hits = [0] * tenants
        self.tenant_misses = [0] * tenants
        # Any object with ``depth_at(step, n_blocks) -> int`` works here
        # (repro.api's UnfreezePolicy protocol); the monotone-boundary
        # contract is still re-checked at runtime in ``round`` regardless of
        # who supplies the depths.
        self.sched = (schedule if schedule is not None
                      else UnfreezeSchedule.from_train_config(tc))
        self.donate = donate
        self.cache: Optional[ActivationCache] = None
        if cache_capacity:
            self.cache = ActivationCache(
                cache_capacity, dtype=cache_dtype,
                sharding=NamedSharding(mesh, P(None, "stage")),
                layout=self.spans)
        self._fns: Dict[Tuple[int, str], Any] = {}  # (boundary, mode) -> jit fn
        self.mode_trace_counts: Dict[Tuple[int, str], int] = {}
        # (boundary, mode) -> {phase: scan length} — the scan lengths XLA
        # actually traced (pipeline._tick_phase reports them); the measured
        # side of the simulator-vs-executor differential harness.
        self.tick_scan_lens: Dict[Tuple[int, str], Dict[str, int]] = {}
        self._last_boundary: Optional[int] = None
        self.step = 0

    # ------------------------------------------------------------------
    def boundary_at(self, step: int) -> int:
        depth = self.sched.depth_at(step, self.cfg.n_layers)
        b = depth_to_boundary(self.cfg, depth)
        return align_boundary(self.spans, b)       # span-aligned (terminator)

    def _fn(self, boundary: int, mode: str = "direct"):
        key = (boundary, mode)
        if key not in self._fns:

            def bump(k=key):
                self.mode_trace_counts[k] = self.mode_trace_counts.get(k, 0) + 1

            def tick_rec(phase, t, k=key):
                self.tick_scan_lens.setdefault(k, {})[phase] = t

            src_dt = (self.cache.src_dtype if self.cache is not None
                      else None)
            fused = make_fused_round(self.cfg, self.tc, self.mesh,
                                     n_stages=self.S, boundary=boundary,
                                     n_micro=self.M, on_trace=bump, mode=mode,
                                     packed=self.packed,
                                     cache_dtype=self.cache_dtype,
                                     cache_src_dtype=src_dt,
                                     spans=self.spans, tick_record=tick_rec,
                                     tenants=self.T)
            donate = (0, 1, 2) if self.donate else ()
            self._fns[key] = jax.jit(fused, donate_argnums=donate)
        return self._fns[key]

    def measured_tick_ledger(self, boundary: int, mode: str = "direct"
                             ) -> Dict[str, int]:
        """Per-round tick totals from the scan lengths actually traced into
        the (boundary, mode) executable — the measured half of the
        simulator-vs-executor differential harness.  Matches the key schema
        of ``pipeline.pipeline_tick_counts`` so tests can compare directly.

        The executable must have been built (one round run, or ``_fn``
        called) — raises KeyError otherwise.
        """
        if (boundary, mode) not in self._fns:
            raise KeyError(
                f"no ({boundary}, {mode!r}) executable built yet — run a "
                f"round at that boundary first")
        rec = self.tick_scan_lens.get((boundary, mode), {})
        S, M = self.S, self.M
        F = frozen_stage_count(self.spans, boundary)
        tb = rec.get("phase_b")
        assert tb is not None, (boundary, mode, rec)
        if "phase_a_packed" in rec:
            a_round = rec["phase_a_packed"]          # one conveyor per round
            a_per_owner = 0                          # hoisted out of the scan
        elif "phase_a" in rec:
            a_round = S * rec["phase_a"]             # traced once, scanned S x
            a_per_owner = rec["phase_a"]
        else:                                        # cached mode or F == 0
            a_round = 0
            a_per_owner = 0
        saved = (S * (M + F - 1) - a_round
                 if "phase_a_packed" in rec and F > 0 else 0)
        return {
            "fwd_ticks": a_per_owner + tb,
            "bwd_ticks": tb,                         # grad reverses the scan
            "frozen_stages": F,
            "hot_stages": S - F,
            "phase_a_round_ticks": a_round,
            "phase_a_saved_ticks": saved,
        }

    @property
    def n_executables(self) -> int:
        return len(self._fns)

    @property
    def trace_counts(self) -> Dict[int, int]:
        """{boundary: traces over every mode} for every built boundary."""
        out = {b: 0 for b, _ in self._fns}
        for (b, _), n in self.mode_trace_counts.items():
            out[b] += n
        return out

    def compile_counts(self) -> Dict[str, int]:
        """{'<boundary>/<mode>': traces} — the bench's per-boundary record."""
        return {f"{b}/{mode}": n
                for (b, mode), n in sorted(self.mode_trace_counts.items())}

    # ------------------------------------------------------------------
    def _entry_shape(self, labels: Array):
        """Global shape of one cache entry for the current batch
        ([S_stage, S_owner, M, mb, seq, D]; dtype is whatever capture stored).
        Multi-tenant entries keep the SAME per-entry shape — each tenant owns
        its own buffer row under its own ``(tenant, slot, boundary)`` key."""
        if self.T > 1:
            _, _, M, mb, seq = labels.shape
        else:
            _, M, mb, seq = labels.shape
        return (self.S, self.S, M, mb, seq, self.cfg.d_model)

    def _keys(self, slot: int, boundary: int):
        """Cache keys for this round: ``(slot, boundary)`` at T=1 (the PR-4
        schema, unchanged); ``(tenant, slot, boundary)`` per tenant at T>1."""
        if self.T == 1:
            return [(slot, boundary)]
        return [(t, slot, boundary) for t in range(self.T)]

    def round(self, tokens: Array, labels: Array, *,
              slot: Optional[int] = None) -> Dict[str, Any]:
        """One training round: every client acts as initiator once.

        tokens/labels: [S, M, mb, seq] per-client local data for this round
        ([S, T, M, mb, seq] when ``tenants > 1`` — axis 1 is the tenant).
        slot: stable batch-slot id (same slot => same examples, the cache-key
        contract; see ``data.pipeline.RingBatcher`` with ``slots_per_epoch``).
        Returns metrics as DEVICE arrays — no host sync.  Use
        ``materialize_metrics`` (or ``float()``) at your logging interval.
        Multi-tenant rounds add ``tenant_losses`` ([T] device array) and hit
        only when EVERY tenant's key is resident (a partial-hit round re-runs
        the shared conveyor once and refreshes all T entries; the per-tenant
        ``index_of`` calls keep per-tenant hit accounting honest).
        """
        boundary = self.boundary_at(self.step)
        if self._last_boundary is not None and boundary > self._last_boundary:
            raise RuntimeError(
                f"unfreeze boundary increased {self._last_boundary} -> "
                f"{boundary} at step {self.step}; RingAda schedules are "
                f"monotone top-down and the activation cache's invalidation "
                f"contract depends on it (see core/unfreeze.py)")
        if (self.cache is not None and self._last_boundary is not None
                and boundary < self._last_boundary):
            self.cache.invalidate()                # boundary drop: all keys dead
        self._last_boundary = boundary

        cache_hit = False
        mode = "direct"
        tenant_losses = None
        use_cache = self.cache is not None and slot is not None
        if use_cache:
            if not self.cache.compatible(self._entry_shape(labels)):
                self.cache.bypasses += 1           # batch doesn't fit the buffer
                use_cache = False

        if use_cache:
            keys = self._keys(slot, boundary)
            rows = [self.cache.index_of(k) for k in keys]
            if self.T > 1:
                for t, r in enumerate(rows):
                    if r is None:
                        self.tenant_misses[t] += 1
                    else:
                        self.tenant_hits[t] += 1
            if all(r is not None for r in rows):
                fn = self._fn(boundary, "cached")
                row_arg = (jnp.int32(rows[0]) if self.T == 1
                           else jnp.asarray(rows, jnp.int32))
                if self.cache_dtype == "int8":
                    (self.stage_blocks, self.shared, self.opt_state,
                     mets) = fn(
                        self.stage_blocks, self.shared, self.opt_state,
                        self.cache.buffer, self.cache.scales,
                        row_arg, labels)
                else:
                    (self.stage_blocks, self.shared, self.opt_state,
                     mets) = fn(
                        self.stage_blocks, self.shared, self.opt_state,
                        self.cache.buffer, row_arg, labels)
                cache_hit = True
                mode = "cached"
            else:
                mode = "capture"
                fn = self._fn(boundary, "capture")
                (self.stage_blocks, self.shared, self.opt_state,
                 mets, h_cap) = fn(
                    self.stage_blocks, self.shared, self.opt_state,
                    tokens, labels)
                if self.T == 1:
                    self.cache.put(keys[0], h_cap)
                else:
                    # h_cap [T, S_stage, S_owner, M, mb, seq, D]: one entry
                    # per tenant, each the T=1 entry shape — a tenant that
                    # already hit gets its (identical) bits refreshed in place.
                    for t, k in enumerate(keys):
                        self.cache.put(k, h_cap[t])
        else:
            fn = self._fn(boundary, "direct")
            (self.stage_blocks, self.shared, self.opt_state,
             mets) = fn(
                self.stage_blocks, self.shared, self.opt_state, tokens, labels)

        if self.T == 1:
            losses, mean_loss = mets
        else:
            losses, mean_loss, tenant_losses = mets

        self.step += self.S
        out = {"loss": mean_loss, "losses": losses,
               "boundary": boundary, "step": self.step,
               "cache_hit": cache_hit, "mode": mode}
        if tenant_losses is not None:
            out["tenant_losses"] = tenant_losses
            out["tenant_cache_hits"] = list(self.tenant_hits)
            out["tenant_cache_misses"] = list(self.tenant_misses)
        if self.cache is not None:
            out.update(self.cache.stats())
        return out

    @staticmethod
    def materialize_metrics(m: Dict[str, Any]) -> Dict[str, Any]:
        """Host-sync a metrics dict (the once-per-logging-interval sync)."""
        return {k: scalarize(v) for k, v in m.items()}

    # ------------------------------------------------------------------
    def repartition(self, spans: Sequence[Span]) -> None:
        """Switch to a new span layout mid-run (the elastic-membership /
        re-profiling hook): restacks the live params AND Adam moments into
        the new padded layout, drops every built executable (the layout is
        static per build), flushes the activation cache (its entries' stage-F
        location is layout-dependent — ``ActivationCache.set_layout``), and
        re-seeds the monotone-boundary check (alignment granularity changed,
        so the span-aligned boundary may legitimately move up toward the raw
        schedule value).
        """
        new = pl.resolve_spans(self.cfg.repeats, self.S, spans)
        if new == self.spans:
            return
        old = self.spans
        if self.T == 1:
            params = self.export_params()            # flat [R, ...] canonical
            m_ad = pl.unstack_entry(self.opt_state["m"]["adapter"], old)
            v_ad = pl.unstack_entry(self.opt_state["v"]["adapter"], old)
            self.spans = new
            self.lps = (self.cfg.repeats // self.S
                        if not pl.is_ragged(new) else None)
            self.stage_blocks, self.shared = pl.place_stage_stack(
                params, self.cfg, self.mesh, spans=new)
            self.opt_state = {
                **self.opt_state,
                "m": {**self.opt_state["m"],
                      "adapter": pl.stack_entry(m_ad, new)},
                "v": {**self.opt_state["v"],
                      "adapter": pl.stack_entry(v_ad, new)},
            }
        else:
            # Restack ALL tenants: backbone once, every tenant's adapters
            # and moments through the tenant-major [T, R, ...] flat form.
            bb_flat = self._unstack_backbone(old)
            ad_flat = self._unstack_adapters(self.stage_blocks["adapter"], old)
            m_flat = self._unstack_adapters(
                self.opt_state["m"]["adapter"], old)
            v_flat = self._unstack_adapters(
                self.opt_state["v"]["adapter"], old)
            self.spans = new
            self.lps = (self.cfg.repeats // self.S
                        if not pl.is_ragged(new) else None)
            self.stage_blocks = {
                **pl.stack_entry(bb_flat, new),
                "adapter": self._stack_adapters(ad_flat, new)}
            self.opt_state = {
                **self.opt_state,
                "m": {**self.opt_state["m"],
                      "adapter": self._stack_adapters(m_flat, new)},
                "v": {**self.opt_state["v"],
                      "adapter": self._stack_adapters(v_flat, new)},
            }
        self._fns.clear()
        if self.cache is not None:
            self.cache.set_layout(new)
        self._last_boundary = None

    # ------------------------------------------------------------------
    # elastic membership: live S -> S-1 shrink / S -> S+1 grow
    # ------------------------------------------------------------------

    def _resolve_new_spans(self, new_S: int,
                           spans: Optional[Sequence[Span]],
                           profiles: Optional[Sequence[DeviceProfile]]
                           ) -> Tuple[Span, ...]:
        R = self.cfg.repeats
        if R < new_S:
            raise ValueError(
                f"cannot run {new_S} stages over {R} blocks")
        if spans is not None:
            return pl.resolve_spans(R, new_S, spans)
        if profiles is not None:
            if len(profiles) != new_S:
                raise ValueError(
                    f"got {len(profiles)} profiles for a {new_S}-stage ring")
            return spans_from_profiles(R, list(profiles))
        return pl.resolve_spans(R, new_S, None)

    def _regeometry(self, new_S: int, new_spans: Tuple[Span, ...]) -> None:
        """Rebuild the executor at a new ring size in place.

        Everything the ring holds is round-trips through its canonical
        (unstacked, host) form: params via ``export_params`` /
        ``load_canonical``, Adam moments via the flat entry form — the same
        exact restack ``repartition`` does, plus a mesh change.  The host
        hop (``np.asarray``) detaches every leaf from the old mesh's
        sharding so the rebuilt stacks place cleanly on the new one.  The
        activation cache is REBOUND, not restored: entry shapes carry S, so
        the old buffer cannot be reused — the next round's capture
        executable refills it (checkpoint-free recovery).  Counters /
        trace histories survive; executables and tick ledgers do not (the
        geometry they were traced for is gone).
        """
        host = lambda t: jax.tree.map(np.asarray, t)
        old = self.spans
        params = host(self.export_params(None if self.T > 1 else 0))
        if self.T == 1:
            m_ad = host(pl.unstack_entry(self.opt_state["m"]["adapter"], old))
            v_ad = host(pl.unstack_entry(self.opt_state["v"]["adapter"], old))
        else:
            m_ad = host(self._unstack_adapters(
                self.opt_state["m"]["adapter"], old))
            v_ad = host(self._unstack_adapters(
                self.opt_state["v"]["adapter"], old))
        m_hd = host(self.opt_state["m"]["head"])
        v_hd = host(self.opt_state["v"]["head"])
        count = np.asarray(self.opt_state["count"])

        self.S = new_S
        self.mesh = pl.make_ring_mesh(new_S)
        self.spans = new_spans
        self.lps = (self.cfg.repeats // new_S
                    if not pl.is_ragged(new_spans) else None)
        self.load_canonical(params)
        stack = ((lambda t: pl.stack_entry(t, new_spans)) if self.T == 1
                 else (lambda t: self._stack_adapters(t, new_spans)))
        self.opt_state = {"m": {"adapter": stack(m_ad), "head": m_hd},
                          "v": {"adapter": stack(v_ad), "head": v_hd},
                          "count": jnp.asarray(count)}
        self._fns.clear()
        self.tick_scan_lens.clear()
        if self.cache is not None:
            self.cache.rebind(
                sharding=NamedSharding(self.mesh, P(None, "stage")),
                layout=new_spans)
        self._last_boundary = None

    def shrink(self, dead_stage: int, *,
               spans: Optional[Sequence[Span]] = None,
               profiles: Optional[Sequence[DeviceProfile]] = None) -> None:
        """Degraded S-1 operation after stage ``dead_stage`` dies.

        The dead device's span is reassigned over the survivors — via
        explicit ``spans``, via ``spans_from_profiles`` over the survivors'
        ``profiles``, or the balanced split.  Nothing is lost to the crash:
        adapters and Adam moments are stage-stacked but every stage's rows
        are recoverable from the canonical round-trip (the donated stacks
        replicate the flat entry form across the SPMD round), so live state
        restacks exactly, the unfreeze boundary aligns DOWN to the new span
        edges, and the activation cache re-captures on the next round —
        no checkpoint restore anywhere on the path.
        """
        if not 0 <= dead_stage < self.S:
            raise ValueError(
                f"dead_stage {dead_stage} out of range for S={self.S}")
        if self.S <= 1:
            raise RuntimeError("cannot shrink a 1-stage ring")
        self._regeometry(self.S - 1,
                         self._resolve_new_spans(self.S - 1, spans, profiles))

    def grow(self, profile: Optional[DeviceProfile] = None, *,
             spans: Optional[Sequence[Span]] = None,
             profiles: Optional[Sequence[DeviceProfile]] = None) -> None:
        """Inverse of ``shrink``: a device joins, S grows by one.

        ``profiles`` (or explicit ``spans``) describe the FULL post-join
        fleet; passing just ``profile`` appends a joining device to an
        otherwise-unprofiled ring (balanced split plus the newcomer's
        speed is meaningless, so that case uses ``spans_from_profiles``
        over unit-speed incumbents + the newcomer).
        """
        new_S = self.S + 1
        if jax.device_count() < new_S:
            raise RuntimeError(
                f"grow to S={new_S} needs {new_S} devices, have "
                f"{jax.device_count()}")
        if profiles is None and spans is None and profile is not None:
            profiles = [DeviceProfile(1.0, float("inf"))] * self.S + [profile]
        self._regeometry(new_S,
                         self._resolve_new_spans(new_S, spans, profiles))

    # ------------------------------------------------------------------
    # canonical <-> stacked forms (tenant-aware)
    # ------------------------------------------------------------------

    def _unstack_backbone(self, spans) -> Dict[str, Any]:
        """Non-adapter stage blocks -> flat [R, ...] leaves."""
        bb = {k: v for k, v in self.stage_blocks.items() if k != "adapter"}
        return pl.unstack_entry(bb, spans)

    def _unstack_adapters(self, stacked: Any, spans) -> Any:
        """[S, T, max_span, ...] leaves -> tenant-major flat [T, R, ...]."""
        t_major = jax.tree.map(lambda x: jnp.moveaxis(x, 1, 0), stacked)
        return pl.unstack_entry(t_major, spans, leading=1)

    def _stack_adapters(self, flat_t: Any, spans) -> Any:
        """Inverse of ``_unstack_adapters``: [T, R, ...] -> [S, T, max_span, ...]."""
        t_major = pl.stack_entry(flat_t, spans, leading=1)
        return jax.tree.map(lambda x: jnp.moveaxis(x, 0, 1), t_major)

    # ------------------------------------------------------------------
    def export_params(self, tenant: Optional[int] = None) -> Dict[str, Any]:
        """Canonical (unstacked) param tree.

        T=1: the familiar single-model tree (``tenant`` must be None or 0).
        T>1 with ``tenant=t``: tenant t's complete single-model tree (shared
        trunk + its adapters + its head) — directly loadable by serving.
        T>1 with ``tenant=None``: the tenant-stacked checkpoint tree —
        adapter leaves [T, R, ...], head leaves [T, ...], trunk unstacked.
        """
        if self.T == 1:
            assert tenant in (None, 0), tenant
            entry = pl.unstack_entry(self.stage_blocks, self.spans)
            return {**self.shared, "blocks": (entry,)}
        bb_flat = self._unstack_backbone(self.spans)
        ad_flat = self._unstack_adapters(self.stage_blocks["adapter"],
                                         self.spans)
        if tenant is None:
            entry = {**bb_flat, "adapter": ad_flat}
            return {**self.shared, "blocks": (entry,)}
        entry = {**bb_flat,
                 "adapter": jax.tree.map(lambda x: x[tenant], ad_flat)}
        shared = {**self.shared,
                  "head": jax.tree.map(lambda x: x[tenant],
                                       self.shared["head"])}
        return {**shared, "blocks": (entry,)}

    # ------------------------------------------------------------------
    def export_adapters(self, tenant: int = 0) -> Dict[str, Any]:
        """One tenant's trainable set as a flat bundle:
        ``{"adapter": [R, ...] tree, "head": head tree}`` — the unit the
        AdapterStore persists and serving hot-swaps."""
        assert 0 <= tenant < self.T, (tenant, self.T)
        if self.T == 1:
            ad = pl.unstack_entry(self.stage_blocks["adapter"], self.spans)
            return {"adapter": ad, "head": self.shared["head"]}
        ad_flat = self._unstack_adapters(self.stage_blocks["adapter"],
                                         self.spans)
        return {"adapter": jax.tree.map(lambda x: x[tenant], ad_flat),
                "head": jax.tree.map(lambda x: x[tenant],
                                     self.shared["head"])}

    def import_adapters(self, tenant: int, bundle: Dict[str, Any]) -> None:
        """Install a flat adapter bundle into tenant ``tenant``'s slot and
        invalidate ONLY that tenant's cache partition (its stage-F inputs may
        now differ; neighbors' entries stay valid)."""
        assert 0 <= tenant < self.T, (tenant, self.T)
        ad_stacked = pl.stack_entry(bundle["adapter"], self.spans)
        if self.T == 1:
            self.stage_blocks = {**self.stage_blocks, "adapter": ad_stacked}
            self.shared = {**self.shared, "head": bundle["head"]}
            if self.cache is not None:
                self.cache.invalidate()
            return
        self.stage_blocks = {
            **self.stage_blocks,
            "adapter": jax.tree.map(
                lambda cur, new: cur.at[:, tenant].set(new),
                self.stage_blocks["adapter"], ad_stacked)}
        self.shared = {
            **self.shared,
            "head": jax.tree.map(lambda cur, new: cur.at[tenant].set(new),
                                 self.shared["head"], bundle["head"])}
        if self.cache is not None:
            self.cache.invalidate_tenant(tenant)

    def export_tenant_opt(self, tenant: int = 0) -> Dict[str, Any]:
        """One tenant's optimizer state in the flat bundle layout (moments
        shaped like ``export_adapters``; ``count`` is the shared step)."""
        assert 0 <= tenant < self.T, (tenant, self.T)

        def flat_moment(tree):
            if self.T == 1:
                return {"adapter": pl.unstack_entry(tree["adapter"],
                                                    self.spans),
                        "head": tree["head"]}
            ad = self._unstack_adapters(tree["adapter"], self.spans)
            return {"adapter": jax.tree.map(lambda x: x[tenant], ad),
                    "head": jax.tree.map(lambda x: x[tenant], tree["head"])}

        return {"m": flat_moment(self.opt_state["m"]),
                "v": flat_moment(self.opt_state["v"]),
                "count": self.opt_state["count"]}

    def import_tenant_opt(self, tenant: int, opt: Dict[str, Any]) -> None:
        """Install flat per-tenant moments (inverse of ``export_tenant_opt``;
        ``count`` is shared ring state and is left untouched at T>1)."""
        assert 0 <= tenant < self.T, (tenant, self.T)

        def set_moment(cur, flat):
            ad_stacked = pl.stack_entry(flat["adapter"], self.spans)
            if self.T == 1:
                return {"adapter": ad_stacked, "head": flat["head"]}
            return {"adapter": jax.tree.map(
                        lambda c, n: c.at[:, tenant].set(n),
                        cur["adapter"], ad_stacked),
                    "head": jax.tree.map(lambda c, n: c.at[tenant].set(n),
                                         cur["head"], flat["head"])}

        new = {"m": set_moment(self.opt_state["m"], opt["m"]),
               "v": set_moment(self.opt_state["v"], opt["v"]),
               "count": (opt["count"] if self.T == 1
                         else self.opt_state["count"])}
        self.opt_state = new

    def load_canonical(self, params: Dict[str, Any]) -> None:
        """Install a canonical tree from ``export_params()`` (T=1 single-model
        or T>1 tenant-stacked) back into the live stage layout."""
        if self.T == 1:
            self.stage_blocks, self.shared = pl.place_stage_stack(
                params, self.cfg, self.mesh, spans=self.spans)
            return
        entry = params["blocks"][0]
        bb_flat = {k: v for k, v in entry.items() if k != "adapter"}
        self.stage_blocks = {
            **pl.stack_entry(bb_flat, self.spans),
            "adapter": self._stack_adapters(entry["adapter"], self.spans)}
        self.shared = {k: params[k] for k in self.shared}
