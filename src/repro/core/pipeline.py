"""RingAda ring pipeline on an SPMD ``stage`` mesh axis (shard_map + ppermute).

The paper's ring of edge devices maps to a mesh axis: stage ``s`` holds repeats
``[s*Lps, (s+1)*Lps)`` of the block stack (params stage-stacked and sharded), plus a
replicated copy of the embedding and head — exactly the paper's deployment.

One *training round* (Algorithm 1, initiator = ``owner``):

  1. The owner embeds its local microbatches and ships them to stage 0 (paper:
     initiator sends embeddings to the client holding the lowest Trm block).
  2. **Phase A — frozen trunk, forward-only streaming**: stages ``[0, F)`` hold only
     frozen adapters (``F = boundary / Lps``). Their tick-pipeline runs entirely
     under ``stop_gradient``: ``M + F - 1`` ticks, never any backward — the paper's
     "clients with all-frozen adapters continuously forward consecutive batches".
  3. **Phase B — hot region, strict 1F1B**: stages ``[F, S)`` run a differentiable
     tick-pipeline (``M + S_hot - 1`` ticks). ``jax.grad`` through the tick scan +
     ``ppermute`` yields the reverse-tick backward pipeline automatically (cotangents
     ppermute backwards along the ring), early-stopping at stage F — the paper's
     *terminator*.
  4. The last stage's outputs return to the owner; the owner computes the loss
     against its local labels (labels never leave their device), the head gradient
     is ``psum``-shared, and adapter gradients stay local to their stage — no
     weight-gradient traffic, matching the paper's communication pattern.

This module provides the ring *round* in two forms, split from the drivers that
consume them (the executor split):

  * ``make_ring_round`` / ``make_ring_train_round`` — the reference path: owner
    is **static**, the owner->stage0 and last->owner hops are static ``ppermute``
    tables, and each (owner, boundary) pair is its own executable.  Driven by
    ``core/ring.py``'s ``RingTrainer`` (S executables per boundary, host-side
    optimizer).
  * ``ring_round_local`` — the fused path: owner is a **traced** scalar, the two
    owner-dependent hops become ``all_gather`` + dynamic-index rotations (a
    dynamic permute), so one executable serves every owner.
    ``core/executor.py``'s ``RingExecutor`` scans this over all S owners and
    runs the stage-masked optimizer *inside* a single donated jit.
    ``ring_round_local`` is itself the composition of two halves,
    ``ring_phase_a`` (embeddings -> stage-``F`` boundary activations) and
    ``ring_phase_b`` (boundary activations -> local loss), exposed separately
    so the executor can cache the Phase-A output.

Packed-conveyor Phase A (``ring_phase_a_packed``):

  The fused executor's owner scan re-enters Phase A once per owner — S
  independent ``M + F - 1``-tick pipelines per round, each paying its own
  ``F - 1``-tick fill/drain bubble.  Because the frozen trunk is constant for
  the whole round, all S owners' streams can instead be packed back-to-back
  into ONE ``S*M + F - 1``-tick conveyor run before the owner scan, which
  then consumes the resulting ``[S, M, ...]`` boundary stack by dynamic
  index.  Saves ``(S-1)*(F-1)`` ticks per round on every direct/capture
  round; capture writes all S owners' boundary activations in one pass.

Phase-A skip (the frozen-trunk activation cache, ``core/actcache.py``):

  Everything Phase A reads — the embedding table, the frozen trunk's backbone
  weights, and the frozen stages' adapters — is *outside* RingAda's trainable
  set while the boundary holds (the optimizer's stage mask keeps frozen
  adapters and their moments bit-identical).  Its output, the stage-``F``
  input activations ``h_B``, is therefore bit-identical across rounds for the
  same microbatches at the same boundary.  ``RingExecutor`` exploits this:
  the first time a batch slot is seen at a boundary it runs a *capture*
  executable (full round, Phase-A outputs written to a donated device ring
  buffer), and on every later visit a *cached* executable enters the pipeline
  directly at stage ``F`` — no embed, no ``all_gather``, none of the
  ``M + F - 1`` frozen-trunk ticks per owner-iteration.

  Invalidation rules: entries are keyed ``(batch_slot, boundary)``.  The
  unfreeze schedule is monotone top-down (``core/unfreeze.py`` rejects
  anything else), so when the boundary drops every cached entry is
  permanently unreachable and the whole cache is dropped in one invalidation.
  Within a boundary segment nothing the cache depends on can change, so no
  finer-grained invalidation exists.  Disable the cache (capacity 0 / no
  batch slots) for streaming or non-repeating data — a slot that is never
  revisited only pays the capture write without ever hitting.

Heterogeneous (ragged) span layouts:

  The paper's coordinator assigns *uneven* contiguous block spans to
  heterogeneous devices (Algorithm 1's 4:5:2:3 example).  Every builder here
  takes ``spans=`` ([(begin, end)] per stage, ``partition.assign_layers``
  output plugs in directly): stage stacks are padded to ``max_span`` with a
  per-stage validity mask (padding rows are clamped duplicates whose
  applications are masked out of the residual stream), so the tick pipeline
  stays ONE traced ``lax.scan`` under SPMD — each stage ticks in lockstep
  applying exactly its own span.  Boundaries must fall on span edges
  (``partition.align_boundary`` rounds down).  Uniform layouts
  (``spans=None``) keep the historical unmasked fast path bit-for-bit.

SPMD adaptation (DESIGN.md §6): per-device *program* asymmetry is impossible under
SPMD, so the paper's per-device savings appear as globally shorter backward tick
scans and absent residual stashes for phase A, uniform across devices. The
discrete-event simulator (core/simulator.py) models the true MPMD overlap
(``scheme='ringada_cached'`` models the cached steady state;
``spmd_tick_round`` predicts the executor's tick ledger for any span layout).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro import scopes
from repro.configs.base import ModelConfig
from repro.core.partition import (Span, frozen_stage_count, normalize_spans,
                                  span_sizes, uniform_assignment)
from repro.models import params as prm
from repro.models import transformer as tfm
from repro.models.blocks import BlockCtx, apply_block

Array = jax.Array


# ---------------------------------------------------------------------------
# Stage-stacked parameters (uniform OR ragged span layouts)
# ---------------------------------------------------------------------------


def resolve_spans(n_blocks: int, n_stages: int,
                  spans: Optional[Sequence[Span]] = None) -> Tuple[Span, ...]:
    """Canonical span layout: the given one (validated against the model) or
    the balanced default.  ``assign_layers`` output plugs in directly."""
    if spans is None:
        spans = uniform_assignment(n_blocks, n_stages)
    spans = normalize_spans(spans, n_blocks)
    if len(spans) != n_stages:
        raise ValueError(
            f"span layout {list(spans)} has {len(spans)} stages, mesh has "
            f"{n_stages}")
    return spans


def span_maps(spans: Sequence[Span]) -> Tuple[np.ndarray, np.ndarray,
                                              np.ndarray, np.ndarray]:
    """Static index maps between the flat [R, ...] block stack and the padded
    [S, max_span, ...] stage stack:

      stack_idx [S, max_span] — global block index feeding stage row (u, j);
        padding rows clamp to the stage's last real block (real weights, so
        masked-out applications can never produce NaNs),
      valid     [S, max_span] — True where row (u, j) holds a real block,
      stage_of  [R]           — owning stage of global block r,
      slot_of   [R]           — row of block r inside its stage's stack.
    """
    sizes = span_sizes(spans)
    S, mx = len(spans), max(sizes)
    R = spans[-1][1]
    stack_idx = np.zeros((S, mx), np.int32)
    valid = np.zeros((S, mx), bool)
    stage_of = np.zeros(R, np.int32)
    slot_of = np.zeros(R, np.int32)
    for u, (b, e) in enumerate(spans):
        n = e - b
        stack_idx[u, :n] = np.arange(b, e)
        stack_idx[u, n:] = e - 1
        valid[u, :n] = True
        stage_of[b:e] = u
        slot_of[b:e] = np.arange(n)
    return stack_idx, valid, stage_of, slot_of


def is_ragged(spans: Sequence[Span]) -> bool:
    return len(set(span_sizes(spans))) > 1


def stack_entry(entry: Any, spans: Sequence[Span], *, leading: int = 0) -> Any:
    """Flat block-entry tree (leaves [R, C, ...]) -> padded stage stack
    (leaves [S, max_span, C, ...]).  Uniform layouts keep the original
    zero-copy reshape; ragged layouts gather through ``span_maps`` (padding
    rows duplicate the stage's last block and are masked in the forward).

    ``leading`` extra axes before the block axis pass through untouched —
    the multi-tenant executor stacks tenant-major ``[T, R, C, ...]`` adapter
    trees with ``leading=1`` (-> ``[T, S, max_span, C, ...]``)."""
    S = len(spans)
    lead = (slice(None),) * leading
    if not is_ragged(spans):
        lps = span_sizes(spans)[0]
        return jax.tree.map(
            lambda x: x.reshape(x.shape[:leading] + (S, lps)
                                + x.shape[leading + 1:]), entry)
    stack_idx, _, _, _ = span_maps(spans)
    idx = jnp.asarray(stack_idx)
    return jax.tree.map(lambda x: x[lead + (idx,)], entry)


def unstack_entry(stacked: Any, spans: Sequence[Span], *,
                  leading: int = 0) -> Any:
    """Inverse of :func:`stack_entry`: padded [S, max_span, C, ...] leaves ->
    flat [R, C, ...] leaves (padding rows dropped).  ``leading`` as in
    :func:`stack_entry`."""
    R = spans[-1][1]
    lead = (slice(None),) * leading
    if not is_ragged(spans):
        return jax.tree.map(
            lambda x: x.reshape(x.shape[:leading] + (R,)
                                + x.shape[leading + 2:]), stacked)
    _, _, stage_of, slot_of = span_maps(spans)
    u, j = jnp.asarray(stage_of), jnp.asarray(slot_of)
    return jax.tree.map(lambda x: x[lead + (u, j)], stacked)


def stage_stack(params: Dict[str, Any], cfg: ModelConfig, n_stages: int, *,
                spans: Optional[Sequence[Span]] = None
                ) -> Tuple[Any, Dict[str, Any]]:
    """Split params into (stage_blocks, shared).

    stage_blocks: block-stack leaves stacked [S, max_span, C, ...] (shard on
    'stage'): stage ``u`` holds blocks ``spans[u]``, rows past its span are
    clamped duplicates masked out of the forward.  ``spans=None`` is the
    balanced split (the classic [S, R/S, C, ...] when R divides evenly).
    shared: embed / final_norm / head (+meta), replicated on every stage — the
    paper keeps Emb + Hed copies on every client.
    """
    assert len(cfg.pattern) == 1, "ring pipeline requires a uniform layer pattern"
    spans = resolve_spans(cfg.repeats, n_stages, spans)
    stage_blocks = stack_entry(params["blocks"][0], spans)
    shared = {k: v for k, v in params.items() if k != "blocks"}
    return stage_blocks, shared


def make_ring_mesh(n_stages: int, *,
                   devices: Optional[Sequence] = None) -> Mesh:
    """Ring-pipeline mesh over the 'stage' axis: one device per stage, taken
    from ``devices`` (default: the backend's devices, first ``n_stages``).
    Auto axis type (``jax.make_mesh`` defaults to Explicit): the ring places
    its arrays with ``NamedSharding`` / ``shard_map`` itself."""
    return jax.make_mesh((n_stages,), ("stage",),
                         axis_types=(AxisType.Auto,), devices=devices)


def stage_shardings(mesh: Mesh) -> Tuple[NamedSharding, NamedSharding]:
    """Where ``stage_stack``'s two outputs live: the block stacks split over
    'stage' (each device holds its own span), the shared leaves replicated."""
    return NamedSharding(mesh, P("stage")), NamedSharding(mesh, P())


def place_stage_stack(params: Dict[str, Any], cfg: ModelConfig, mesh: Mesh, *,
                      spans: Optional[Sequence[Span]] = None
                      ) -> Tuple[Any, Dict[str, Any]]:
    """``stage_stack`` of a canonical tree, written straight into
    ``stage_shardings(mesh)`` (no stacked copy on the default device)."""
    S = mesh.shape["stage"]
    return jax.jit(lambda p: stage_stack(p, cfg, S, spans=spans),
                   out_shardings=stage_shardings(mesh))(params)


def init_stage_stack(cfg: ModelConfig, mesh: Mesh, key: Array, *,
                     spans: Optional[Sequence[Span]] = None
                     ) -> Tuple[Any, Dict[str, Any]]:
    """Seeded weights built directly where they live: one jit materializes
    and stacks them with ``stage_shardings(mesh)`` as its output placement,
    so the canonical tree exists only inside the program and XLA generates
    each stage's blocks on that stage's device.  Bit-identical to
    ``stage_stack(prm.materialize(param_defs(cfg), key, cfg.dtype), ...)``."""
    S = mesh.shape["stage"]
    defs = prm.param_defs(cfg)
    return jax.jit(
        lambda k: stage_stack(prm.materialize(defs, k, cfg.dtype), cfg, S,
                              spans=spans),
        out_shardings=stage_shardings(mesh))(key)


def unstack(stage_blocks, cfg: ModelConfig, params: Dict[str, Any],
            shared: Dict[str, Any], *,
            spans: Optional[Sequence[Span]] = None) -> Dict[str, Any]:
    """Inverse of stage_stack: rebuild the flat [R, C, ...] param tree."""
    n_stages = len(jax.tree.leaves(stage_blocks)[0])
    spans = resolve_spans(cfg.repeats, n_stages, spans)
    entry = unstack_entry(stage_blocks, spans)
    return {**params, **shared, "blocks": (entry,)}


# ---------------------------------------------------------------------------
# Per-stage layer application
# ---------------------------------------------------------------------------


def _apply_stage_layers(cfg: ModelConfig, stage_params, h: Array,
                        positions: Array, valid: Optional[Array] = None
                        ) -> Array:
    """Apply this stage's local blocks (leaves [max_span, C, ...]) to h
    [mb, seq, D].  ``valid`` ([max_span] bool, stage-local) masks padding
    rows of a ragged span layout: an invalid row's application is discarded
    (the residual stream passes through unchanged), so every stage scans the
    same ``max_span`` slots under SPMD while computing exactly its own span.
    ``valid=None`` (uniform layouts) keeps the unmasked fast path."""
    ctx = BlockCtx(cfg=cfg, mode="seq", positions=positions, causal=True,
                   q_chunk=tfm.pick_chunk(h.shape[1]))
    kind = cfg.pattern[0][0]

    def body(carry, xs):
        p_slice = xs if valid is None else xs[0]

        def inner(c2, p2):
            h3, _, _ = apply_block(kind, cfg, p2, c2, ctx, None)
            return h3, None

        h2, _ = lax.scan(inner, carry, p_slice)
        if valid is not None:
            h2 = jnp.where(xs[1], h2, carry)
        return h2, None

    xs = stage_params if valid is None else (stage_params, valid)
    h, _ = lax.scan(body, h, xs)
    return h


def _tick_phase(cfg: ModelConfig, s: Array, pos: Array, fwd_perm, n_micro: int,
                blocks_slice, h_inject: Array, first_stage, depth: int,
                valid: Optional[Array] = None, record=None) -> Array:
    """Tick pipeline over stages [first, first+depth); returns the
    [M, mb, seq, D] outputs emitted by stage first+depth-1 (stage-local:
    only meaningful on that stage).  ``record`` (if given) is called with the
    scan length at trace time — the executor's measured tick ledger."""
    M = n_micro
    T = M + depth - 1
    if record is not None:
        record(T)
    rel = s - first_stage

    def tick(carry, t):
        buf = carry
        inject = (rel == 0) & (t < M)
        incoming = jnp.where(inject, h_inject[jnp.minimum(t, M - 1)], buf)
        active = (rel >= 0) & (rel < depth) & (t - rel >= 0) & (t - rel < M)
        out = _apply_stage_layers(cfg, blocks_slice, incoming, pos, valid)
        out = jnp.where(active, out, incoming)
        nxt = lax.ppermute(out, "stage", fwd_perm)
        return nxt, out

    _, emits = lax.scan(tick, jnp.zeros_like(h_inject[0]), jnp.arange(T))
    take = jnp.arange(M) + depth - 1
    return emits[take]                                         # [M, mb, seq, D]


# ---------------------------------------------------------------------------
# One RingAda round as a shard_map'd, differentiable function (static owner)
# ---------------------------------------------------------------------------


def _stage_valid(spans, s) -> Optional[Array]:
    """Stage-local [max_span] validity row for ragged layouts (None when the
    layout is uniform — the unmasked fast path stays bit-identical)."""
    if not is_ragged(spans):
        return None
    _, valid, _, _ = span_maps(spans)
    return jnp.asarray(valid)[s]


def make_ring_round(cfg: ModelConfig, mesh: Mesh, *, n_stages: int, owner: int,
                    boundary: int, n_micro: int,
                    spans: Optional[Sequence[Span]] = None):
    """Build ``loss_fn(stage_blocks, shared, tokens, labels) -> loss``.

    Static per build: (owner, boundary, spans). boundary must be span-aligned
    (fall on a stage edge of ``spans``; stage-aligned in the uniform case).
    Global input shapes:
      stage_blocks leaves [S, max_span, C, ...] sharded P('stage')
      shared                                  replicated P()
      tokens / labels [S, M, mb, seq]         sharded P('stage')  (per-client data)
    """
    spans = resolve_spans(cfg.repeats, n_stages, spans)
    F = frozen_stage_count(spans, boundary)
    S_hot = n_stages - F
    M = n_micro
    fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def round_fn(stage_blocks, shared, tokens, labels):
        s = lax.axis_index("stage")
        my_blocks = jax.tree.map(lambda x: x[0], stage_blocks)  # [max_span,...]
        valid = _stage_valid(spans, s)
        my_tokens = tokens[0]                                     # [M, mb, seq]
        my_labels = labels[0]
        mb, seq = my_tokens.shape[1], my_tokens.shape[2]
        pos = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32)[None], (mb, seq))

        # 1. owner embeds; one static hop owner -> stage 0
        emb_all = jax.vmap(lambda t: tfm.embed(cfg, shared, t, pos))(my_tokens)
        shift0 = [(i, (i - owner) % n_stages) for i in range(n_stages)]
        emb_at0 = lax.ppermute(emb_all, "stage", shift0)

        phase = lambda blocks_slice, h_inject, first, depth: _tick_phase(
            cfg, s, pos, fwd_perm, M, blocks_slice, h_inject, first, depth,
            valid)

        # 2. Phase A (forward-only streaming, no autodiff possible by construction)
        if F > 0:
            outs_A = phase(lax.stop_gradient(my_blocks),
                           lax.stop_gradient(emb_at0), 0, F)
            outs_A = lax.stop_gradient(outs_A)
            h_B = lax.ppermute(outs_A, "stage", fwd_perm)          # stage F-1 -> F
        else:
            h_B = emb_at0

        # 3. Phase B (hot 1F1B pipeline; grad => reverse ticks, stops at stage F)
        outs_B = phase(my_blocks, h_B, F, S_hot)

        # 4. back to the owner; loss on the owner's local labels
        shift_back = [(i, (i - (n_stages - 1) + owner) % n_stages)
                      for i in range(n_stages)]
        finals = lax.ppermute(outs_B, "stage", shift_back)
        logits = jax.vmap(lambda hh: tfm.head(cfg, shared, hh))(finals)
        lf = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(lf, axis=-1)
        gold = jnp.take_along_axis(lf, my_labels[..., None], axis=-1)[..., 0]
        is_owner = (s == owner).astype(jnp.float32)
        loss = jnp.mean(lse - gold) * is_owner
        return lax.psum(loss, "stage")

    return jax.shard_map(round_fn, mesh=mesh,
                         in_specs=(P("stage"), P(), P("stage"), P("stage")),
                         out_specs=P())


def make_ring_train_round(cfg: ModelConfig, mesh: Mesh, *, n_stages: int,
                          owner: int, boundary: int, n_micro: int,
                          spans: Optional[Sequence[Span]] = None):
    """Returns fn(stage_blocks, shared, tokens, labels) ->
    (loss, (adapter_grads [S,max_span,C,...] stage-local, head_grads
    replicated))."""
    loss_fn = make_ring_round(cfg, mesh, n_stages=n_stages, owner=owner,
                              boundary=boundary, n_micro=n_micro, spans=spans)

    def train_round(stage_blocks, shared, tokens, labels):
        def wrapped(adapters, head_p):
            blocks2 = {**stage_blocks, "adapter": adapters}
            shared2 = {**shared, "head": head_p}
            return loss_fn(blocks2, shared2, tokens, labels)

        loss, grads = jax.value_and_grad(wrapped, argnums=(0, 1))(
            stage_blocks["adapter"], shared["head"])
        return loss, grads

    return train_round


# ---------------------------------------------------------------------------
# One RingAda round as a *local* function with a traced owner (fused path)
# ---------------------------------------------------------------------------


def gather_embeddings(cfg: ModelConfig, shared: Dict[str, Any],
                      my_tokens: Array, pos: Array) -> Array:
    """All stages' embedded microbatches, gathered once per round.

    The embedding table is outside RingAda's trainable set (adapters + head),
    so within a round the embeddings are round-constant: the fused executor
    hoists this single ``all_gather`` out of the owner scan instead of paying
    an owner->stage0 hop per iteration.  Returns [S, M, mb, seq, D]."""
    with jax.named_scope(scopes.TRUNK):
        emb_all = jax.vmap(lambda t: tfm.embed(cfg, shared, t, pos))(my_tokens)
        return lax.all_gather(emb_all, "stage")


def _ring_geometry(cfg: ModelConfig, n_stages: int, boundary: int,
                   spans: Optional[Sequence[Span]] = None
                   ) -> Tuple[Tuple[Span, ...], int]:
    """(canonical spans, frozen-stage count F) for a span-aligned boundary."""
    spans = resolve_spans(cfg.repeats, n_stages, spans)
    return spans, frozen_stage_count(spans, boundary)


def ring_phase_a(cfg: ModelConfig, *, n_stages: int, boundary: int,
                 n_micro: int, spans: Optional[Sequence[Span]] = None,
                 record=None):
    """Phase A of the local round: embeddings -> stage-``F`` boundary inputs.

    Returns ``fn(owner, my_blocks, emb_g) -> h_B`` ([M, mb, seq, D]
    stage-local), where ``h_B`` is exactly what Phase B injects at stage F:
    the frozen trunk's outputs after the F-1 -> F hop (or, at boundary 0, the
    owner's embeddings dynamically rotated to stage 0).  Always emitted under
    ``stop_gradient`` — the trunk is frozen by construction, which is also
    what makes ``h_B`` cacheable across rounds (see module docstring).
    """
    S = n_stages
    spans, F = _ring_geometry(cfg, n_stages, boundary, spans)
    fwd_perm = [(i, (i + 1) % S) for i in range(S)]

    @jax.named_scope(scopes.TRUNK)
    def phase_a(owner, my_blocks, emb_g):
        s = lax.axis_index("stage")
        valid = _stage_valid(spans, s)
        seq = emb_g.shape[3]
        mb = emb_g.shape[2]
        pos = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32)[None], (mb, seq))

        # owner -> stage 0: stage j reads stage (j+owner)'s embeddings
        emb_at0 = lax.dynamic_index_in_dim(emb_g, (s + owner) % S, 0,
                                           keepdims=False)
        if F > 0:
            outs_A = _tick_phase(cfg, s, pos, fwd_perm, n_micro,
                                 lax.stop_gradient(my_blocks),
                                 lax.stop_gradient(emb_at0), 0, F,
                                 valid, record)
            outs_A = lax.stop_gradient(outs_A)
            h_B = lax.ppermute(outs_A, "stage", fwd_perm)
        else:
            h_B = emb_at0
        return lax.stop_gradient(h_B)

    return phase_a


def ring_phase_a_packed(cfg: ModelConfig, *, n_stages: int, boundary: int,
                        n_micro: int, spans: Optional[Sequence[Span]] = None,
                        record=None, n_tenants: int = 1):
    """Packed-conveyor Phase A: ALL owners' boundary inputs in one pipeline.

    The per-owner ``ring_phase_a`` runs S independent ``M + F - 1``-tick
    pipelines per round (one inside each owner-iteration of the executor's
    scan), so each owner re-pays the ``F - 1``-tick fill/drain bubble.  But
    everything Phase A reads is frozen for the whole round — the stage-masked
    optimizer keeps frozen adapters bit-identical across owner-iterations —
    so nothing forces the streams apart: this builder concatenates all S
    owners' microbatches into one continuous ``S*M``-deep injection stream
    and runs a single ``S*M + F - 1``-tick conveyor, the paper's "clients
    with all-frozen adapters continuously forward consecutive batches" taken
    across initiators.  Per round that saves ``(S-1)*(F-1)`` ticks
    (``pipeline_tick_counts(packed=True)`` pins both formulas against the
    discrete-event simulator).

    Returns ``fn(my_blocks, emb_g) -> h_B_all`` ([S_owner, M, mb, seq, D]
    stage-local): owner ``o``'s slice is bit-for-bit what ``ring_phase_a``
    would have produced for that owner (same per-microbatch op sequence, only
    the conveyor length differs), emitted under ``stop_gradient``.  There is
    no ``owner`` argument — the executor indexes the stack inside its owner
    scan, and capture mode writes the whole stack to the cache in one pass.

    Multi-tenant (``n_tenants=T > 1``): ``emb_g`` carries a tenant axis —
    [S_owner, T, M, mb, seq, D] — and the pack axis extends from S owners to
    T·S tenant-owners: one continuous ``T*S*M + F - 1``-tick conveyor moves
    every tenant-owner microbatch of the round (slot ``t*S*M + o*M + m`` is
    tenant t / owner o / microbatch m — tenant-major, i.e. tenant 0's PR-4
    stream followed by tenant 1's, ...).  This is valid for the same reason
    the single-tenant pack is: the trunk is frozen for the whole round AND
    bit-identical across tenants (the stage-masked optimizer's frozen-region
    invariant extends across the tenant axis — every tenant's frozen adapter
    rows stay at their shared init), so nothing forces the T·S streams
    apart.  Per-tick shapes are EXACTLY the single-tenant conveyor's
    ([mb, seq, D] per stage), so each microbatch sees a bit-identical op
    sequence to its own single-tenant run — only the conveyor length
    differs; tests/test_tenants.py pins the joint-vs-independent oracle on
    this.  Per tenant the round pays ``S*M + (F-1)/T`` ticks instead of
    ``S*M + F - 1``: the fill/drain bubble is paid once across all T·S·M
    microbatches (the amortization ``benchmarks/pipeline_bench.py`` gates).
    Output: [S_owner, T, M, mb, seq, D].
    """
    S = n_stages
    spans, F = _ring_geometry(cfg, n_stages, boundary, spans)
    M = n_micro
    T = n_tenants
    fwd_perm = [(i, (i + 1) % S) for i in range(S)]

    @jax.named_scope(scopes.TRUNK)
    def phase_a_packed(my_blocks, emb_g):
        s = lax.axis_index("stage")
        valid = _stage_valid(spans, s)
        seq = emb_g.shape[-2]
        mb = emb_g.shape[-3]
        pos = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32)[None],
                               (mb, seq))

        # Owner-major injection stream: conveyor slot o*M + m carries owner
        # o's microbatch m (tenant-major ``t*S*M + o*M + m`` at T > 1).
        # ``emb_g`` is the all_gather'd (replicated) embedding stack and only
        # the rel-0 stage of the tick pipeline ever reads its injection
        # (``_tick_phase`` masks every other stage), so stage 0 reading
        # ``emb_g[o, m]`` is exactly ``ring_phase_a``'s owner -> stage-0
        # dynamic permute for every owner at once.
        if T == 1:
            inject = emb_g.reshape((S * M,) + emb_g.shape[2:])
        else:
            # [S, T, M, mb, seq, D] -> [T, S, M, ...] -> [T*S*M, mb, seq, D]
            e = jnp.swapaxes(emb_g, 0, 1)
            inject = e.reshape((T * S * M,) + e.shape[3:])
        if F > 0:
            outs = _tick_phase(cfg, s, pos, fwd_perm, T * S * M,
                               lax.stop_gradient(my_blocks),
                               lax.stop_gradient(inject), 0, F,
                               valid, record)
            outs = lax.stop_gradient(outs)
            h = lax.ppermute(outs, "stage", fwd_perm)      # stage F-1 -> F
        else:
            h = inject
        if T == 1:
            out = h.reshape((S, M) + emb_g.shape[2:])
        else:
            out = jnp.swapaxes(
                h.reshape((T, S, M) + emb_g.shape[3:]), 0, 1)
        return lax.stop_gradient(out)

    return phase_a_packed


def ring_phase_b(cfg: ModelConfig, *, n_stages: int, boundary: int,
                 n_micro: int, spans: Optional[Sequence[Span]] = None,
                 record=None):
    """Phase B of the local round: stage-``F`` inputs -> local masked loss.

    Returns ``fn(owner, my_blocks, shared, h_B, my_labels) -> local_loss``.
    This is the only differentiable half: the hot 1F1B tick pipeline over
    stages [F, S), the last-stage -> owner hop, and the owner-local loss.
    ``h_B`` may come from ``ring_phase_a`` live or from the activation cache —
    the cache stores exactly the bits the capturing executable computed, and
    nothing Phase A reads changes while the boundary holds (differently-fused
    executables may still differ in float ulps; tests pin allclose).
    """
    spans, F = _ring_geometry(cfg, n_stages, boundary, spans)
    S = n_stages
    S_hot = S - F
    M = n_micro
    fwd_perm = [(i, (i + 1) % S) for i in range(S)]
    # stacked static tables: branch o ships stage S-1's outputs home to owner o
    back_tables = [[(i, (i - (S - 1) + o) % S) for i in range(S)]
                   for o in range(S)]

    def phase_b(owner, my_blocks, shared, h_B, my_labels):
        s = lax.axis_index("stage")
        valid = _stage_valid(spans, s)
        mb, seq = my_labels.shape[1], my_labels.shape[2]
        pos = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32)[None], (mb, seq))

        # hot 1F1B pipeline; grad => reverse ticks, stops at stage F
        with jax.named_scope(scopes.HOT):
            outs_B = _tick_phase(cfg, s, pos, fwd_perm, M, my_blocks, h_B, F,
                                 S_hot, valid, record)

        # last stage -> owner: switch over the stacked static tables
        finals = lax.switch(
            owner,
            [lambda h, t=tbl: lax.ppermute(h, "stage", t) for tbl in back_tables],
            outs_B)
        with jax.named_scope(scopes.HEAD):
            logits = jax.vmap(lambda hh: tfm.head(cfg, shared, hh))(finals)
            lf = logits.astype(jnp.float32)
            lse = jax.nn.logsumexp(lf, axis=-1)
            gold = jnp.take_along_axis(lf, my_labels[..., None],
                                       axis=-1)[..., 0]
        is_owner = (s == owner).astype(jnp.float32)
        return jnp.mean(lse - gold) * is_owner           # LOCAL (not psum'd)

    return phase_b


def ring_round_local(cfg: ModelConfig, *, n_stages: int, boundary: int,
                     n_micro: int, spans: Optional[Sequence[Span]] = None):
    """Local (per-shard) RingAda round with a **traced** owner.

    Returns ``fn(owner, my_blocks, shared, emb_g, my_labels) -> local_loss``
    meant to be called *inside* an existing shard_map over 'stage' (arguments
    already stage-local: my_blocks leaves [lps, C, ...]; ``emb_g`` is
    ``gather_embeddings``' [S, M, mb, seq, D] round-constant embedding stack).

    Owner enters as a traced i32 scalar, so ONE executable serves every owner
    and the executor can ``lax.scan`` over owners inside a single jit.  The
    owner-dependent static ppermute tables of ``make_ring_round`` become

      * owner -> stage 0: a dynamic index into the pre-gathered embeddings
        (stage j reads stage (j+owner)'s microbatches — a dynamic permute), and
      * last stage -> owner: ``lax.switch`` over the S precomputed static
        ppermute tables (all branches compile once; only the owner's executes).

    The returned loss is the **local** masked contribution (nonzero only on the
    owner stage), NOT psum'd: differentiate it directly — the collective
    transposes (ppermute inverse, scatter-sum) route cotangents across stages
    so the per-stage grads equal the reference path's.  psum the values (once
    per round) and the head grads (once per iteration) afterwards.

    Composition of ``ring_phase_a`` and ``ring_phase_b`` (the executor calls
    the halves directly so it can capture / reuse the Phase-A output).
    """
    phase_a = ring_phase_a(cfg, n_stages=n_stages, boundary=boundary,
                           n_micro=n_micro, spans=spans)
    phase_b = ring_phase_b(cfg, n_stages=n_stages, boundary=boundary,
                           n_micro=n_micro, spans=spans)

    def local_fn(owner, my_blocks, shared, emb_g, my_labels):
        h_B = phase_a(owner, my_blocks, emb_g)
        return phase_b(owner, my_blocks, shared, h_B, my_labels)

    return local_fn


def pipeline_tick_counts(n_stages: int, n_micro: int, boundary: int,
                         lps: Optional[int] = None, *, cached: bool = False,
                         packed: bool = False,
                         spans: Optional[Sequence[Span]] = None
                         ) -> Dict[str, int]:
    """Analytic tick counts (used by tests and the §Perf log).

    PipeAdapter (boundary 0): fwd M+S-1, bwd M+S-1.
    RingAda: fwd (M+F-1) + (M+S_hot-1) + 1 hop, bwd M+S_hot-1.
    RingAda + actcache steady state (``cached=True``): the whole Phase-A tick
    scan vanishes — fwd M+S_hot-1 only, bwd unchanged.
    RingAda + packed conveyor (``packed=True``, ``ring_phase_a_packed``):
    Phase A leaves the owner-iteration — all S owners' frozen-trunk streams
    run once per ROUND as one ``S*M + F - 1``-tick conveyor instead of S
    separate ``M + F - 1``-tick pipelines (``S*(M+F-1)`` ticks), saving
    ``(S-1)*(F-1)`` fill/drain bubble ticks per round.

    ``fwd_ticks``/``bwd_ticks`` are per owner-iteration (Phase A excluded
    when it is hoisted or skipped); ``phase_a_round_ticks`` is the whole
    round's Phase-A conveyor length and ``phase_a_saved_ticks`` the packed
    scheme's per-round saving — both pinned against the discrete-event
    simulator in tests/test_simulator.py.

    Pass either ``lps`` (uniform layouts: ``F = boundary // lps``) or
    ``spans`` (ragged layouts: ``F`` = frozen stages of the span-aligned
    boundary).  Tick counts are in STAGE ticks — under SPMD every stage's
    tick applies ``max_span`` block slots (padding masked), so the counts
    are layout-shape-independent given ``F``; tests/test_partition_exec.py
    pins them against the executor's measured scan lengths per layout.
    """
    if spans is not None:
        assert lps is None or lps * n_stages == normalize_spans(spans)[-1][1], \
            "pass lps or spans, not disagreeing both"
        F = frozen_stage_count(normalize_spans(spans), boundary)
    else:
        assert lps is not None, "pass lps (uniform) or spans (ragged)"
        F = boundary // lps
    S_hot = n_stages - F
    phase_a = 0 if (cached or packed or F == 0) else n_micro + F - 1
    if cached or F == 0:
        a_round = 0
    elif packed:
        a_round = n_stages * n_micro + F - 1
    else:
        a_round = n_stages * (n_micro + F - 1)
    saved = ((n_stages - 1) * (F - 1)
             if (packed and not cached and F > 0) else 0)
    return {
        "fwd_ticks": phase_a + n_micro + S_hot - 1,
        "bwd_ticks": n_micro + S_hot - 1,
        "frozen_stages": F,
        "hot_stages": S_hot,
        "phase_a_round_ticks": a_round,
        "phase_a_saved_ticks": saved,
    }
