"""Smoke run of the RingAda trainer on a TPU at StableLM-3B's published width.

    python chip_smoke.py             # one chip: phases (a) and (b)
    python chip_smoke.py --chips 4   # four chips: phase (c) only

All 32 blocks of ``stablelm-3b`` (d_model 2560, 32 heads, d_ff 6912, vocab
50304) in bf16, with random weights made from ``--seed``, driven through
``RingSession.create``:

  (a) the fused ring at S=1 (``backend="fused"``), 3 rounds, one microbatch
      of 2048 tokens — the largest M x seq the v5e compiler admits beside
      the ring's own weights and moments (M=2 x 1024 does not fit);
  (b) the single-worker pjit baseline at depth 1 (boundary 31), 3 steps of
      8 x 1024 tokens, so backprop stops at the boundary on the chip;
  (c) the cached ring at S=4, 8 blocks per chip, M=4 x 1024 tokens,
      ``slots_per_epoch=2``: capture, capture, cached at F=3.

Each phase checks that every loss is finite and compares the first owner's
first loss with plain single-device programs (``models.transformer`` +
``losses.cross_entropy``, no ring code) on the same weights and tokens,
taken before the first round updates them: each ring phase with the same
forward under value_and_grad over the ring's trainable set, within
``TRAIN_LOSS_TOL``, and with the forward alone, within ``LOSS_TOL``; the
pjit phase with the forward alone, within ``PJIT_LOSS_TOL``.  Each ring
phase is followed by one round of the same ring in f32 at HIGHEST matmul
precision with the depth cut to 4 blocks, held to ``F32_LOSS_TOL`` by both
references: bf16 rounding cannot hide a semantic fault there.  Round
seconds are printed as smoke timings only.

The script needs a TPU: when ``jax.devices()[0].platform`` is anything else
it exits non-zero and prints no result.  On success the last line of
standard output is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch.compile_cache import use_compile_cache  # noqa: E402

# Two plain references on the executor's live weights, same tokens:
#  * trained: ``transformer.forward`` + ``cross_entropy`` under
#    value_and_grad over the ring's trainable set at the ring's boundary,
#    the same computation the ring's own loss comes from;
#  * forward: the same forward alone, no grad.
# On a v5e in bf16 the ring at S=1 (all 32 blocks hot) read 0.0113 from the
# forward-only loss, while the pjit step (one hot block) read 3.62e-5 from
# it; dropping one block moved the forward-only loss by 3.3e-3 to 1.7e-2.
# TRAIN_LOSS_TOL holds the ring to the like-for-like program, below that
# one-block shift; LOSS_TOL bounds the forward-only gap at 2.6x the reading.
TRAIN_LOSS_TOL = 1e-3
LOSS_TOL = 3e-2
# The same ring in f32 at HIGHEST matmul precision, depth cut to 4 blocks:
# rounding no longer hides a semantic difference, both references apply.
F32_LOSS_TOL = 1e-4
F32_BLOCKS = 4
# |pjit step - plain forward| on the same batch: the pjit step runs the same
# single-device forward (31 blocks under stop_gradient, one hot), measured
# 3.62e-5 apart on a v5e; 1e-3 stays below one lost block's shift.
PJIT_LOSS_TOL = 1e-3

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class Failed(Exception):
    """A phase's check did not hold."""


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (all threads)."""

    def __init__(self, jax):
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event in COMPILE_EVENTS:
            self.secs += secs


def _gib(n: int) -> str:
    return f"{n / 2**30:.3f}"


def device_bytes(jax, key: str):
    return [d.memory_stats()[key] for d in jax.devices()]


def model_bytes(cfg) -> int:
    from repro.models import params as prm
    import jax.numpy as jnp

    return prm.count_params(prm.param_defs(cfg)) * jnp.dtype(cfg.dtype).itemsize


def plain_losses(jax, cfg, drv, tokens, labels, boundary: int):
    """First owner's (forward, trained) losses from plain single-device
    programs on the executor's live weights (see ``TRAIN_LOSS_TOL``).

    One stage: ``transformer.forward`` itself, in one jit, with
    ``boundary`` frozen blocks for the trained loss.  Several stages: the
    same embed / block scan / head, each stage's blocks run on the chip that
    holds them, the hot last stage under value_and_grad — one jit over the
    stage-sharded weights makes XLA gather the whole model onto every chip
    (5.4 GiB of temporaries per chip in a v5e:2x2 compile), which is what
    the ring exists to avoid.  The grads' sum of squares is returned, so
    the backward is kept without holding a second copy of the head.
    """
    from repro.core import pipeline as pl
    from repro.core import training
    from repro.models import transformer as tfm
    from repro.models.blocks import BlockCtx
    from repro.models.losses import cross_entropy

    def sum_squares(tree):
        return sum(jax.numpy.sum(jax.numpy.square(g.astype("float32")))
                   for g in jax.tree.leaves(tree))

    M, mb, seq = tokens.shape
    toks = tokens.reshape(M * mb, seq)
    labs = labels.reshape(M * mb, seq)

    if drv.S == 1:
        def canon(stage_blocks, shared):
            return {**shared,
                    "blocks": (pl.unstack_entry(stage_blocks, drv.spans),)}

        def fwd(stage_blocks, shared, t, lab):
            logits, _ = tfm.forward(canon(stage_blocks, shared), t, cfg)
            return cross_entropy(logits, lab)[0]

        def trained(stage_blocks, shared, t, lab):
            params = canon(stage_blocks, shared)

            def loss(tr):
                logits, _ = tfm.forward(params, t, cfg, boundary=boundary,
                                        hot_adapters=tr["adapters"],
                                        head_params=tr["head"])
                return cross_entropy(logits, lab)[0]

            value, grads = jax.value_and_grad(loss)(
                training.split_trainable(params, boundary))
            return value, sum_squares(grads)

        args = (drv.stage_blocks, drv.shared, toks, labs)
        return (float(jax.jit(fwd)(*args)),
                float(jax.jit(trained)(*args)[0]))

    hot = [u for u, (lo, _) in enumerate(drv.spans) if lo >= boundary]
    if hot != [drv.S - 1]:
        raise Failed(f"plain reference needs exactly the last stage hot, "
                     f"got stages {hot} at boundary {boundary}")

    def ctx_for(t):
        pos = jax.numpy.broadcast_to(jax.numpy.arange(seq)[None], t.shape[:2])
        return pos, BlockCtx(cfg=cfg, mode="seq", positions=pos, causal=True,
                             q_chunk=tfm.pick_chunk(seq))

    def on(tree, dev):
        """This device's own shard of every leaf (no copy)."""
        return jax.tree.map(
            lambda x: next(s.data for s in x.addressable_shards
                           if s.device == dev), tree)

    def embed(shared, t):
        return tfm.embed(cfg, shared, t, ctx_for(t)[0])

    def blocks(stage_shard, h):
        entry = jax.tree.map(lambda x: x[0], stage_shard)   # [1, lps, ...]
        h, _, _ = tfm._run_repeats(cfg, (entry,), h, tfm._ZERO_AUX(),
                                   ctx_for(h)[1])
        return h

    def head_loss(shared, h, lab):
        return cross_entropy(tfm.head(cfg, shared, h), lab)[0]

    def trained_last(stage_shard, shared, h, lab):
        def loss(ad, head):
            return head_loss({**shared, "head": head},
                             blocks({**stage_shard, "adapter": ad}, h), lab)

        value, grads = jax.value_and_grad(loss, argnums=(0, 1))(
            stage_shard["adapter"], shared["head"])
        return value, sum_squares(grads)

    devs = list(drv.mesh.devices.flat)
    h = jax.jit(embed)(on(drv.shared, devs[0]), jax.device_put(toks, devs[0]))
    run_blocks = jax.jit(blocks)
    for dev in devs[:-1]:
        h = run_blocks(on(drv.stage_blocks, dev), jax.device_put(h, dev))
    last = devs[-1]
    blk, shd = on(drv.stage_blocks, last), on(drv.shared, last)
    h, lab = jax.device_put(h, last), jax.device_put(labs, last)
    fwd_loss = jax.jit(head_loss)(shd, run_blocks(blk, h), lab)
    train_loss, _ = jax.jit(trained_last)(blk, shd, h, lab)
    return float(fwd_loss), float(train_loss)


def check_close(name: str, got: float, refs) -> None:
    """``refs``: (label, value, tol) triples; all are printed before any
    failure is raised."""
    bad = []
    for ref, want, tol in refs:
        diff = abs(got - want)
        print(f"[{name}] first owner's round-0 loss {got!r} vs {ref} "
              f"{want!r}: |diff| {diff!r} (tol {tol})", flush=True)
        if not diff <= tol:
            bad.append(f"{ref} {want}")
    if bad:
        raise Failed(f"{name}: loss {got} vs {', '.join(bad)}")


def check_finite(name: str, losses) -> None:
    if not all(math.isfinite(x) for x in losses):
        raise Failed(f"{name}: non-finite loss in {losses}")


def run_ring(jax, clock, name: str, cfg, tc, *, backend: str, n_stages: int,
             rounds: int, slots_per_epoch=None, modes=None,
             tols=(TRAIN_LOSS_TOL, LOSS_TOL)) -> None:
    from repro.api import RingSession

    c0 = clock.secs
    sess = RingSession.create(cfg, tc, backend=backend, n_stages=n_stages,
                              slots_per_epoch=slots_per_epoch)
    drv = sess.backend.driver
    in_use = device_bytes(jax, "bytes_in_use")
    print(f"[{name}] set-up: backend={backend} S={n_stages} "
          f"spans={[list(s) for s in drv.spans]} bytes_in_use per chip (GiB) "
          f"{[_gib(b) for b in in_use]}", flush=True)
    if n_stages > 1:
        whole = model_bytes(cfg)
        if not max(in_use) < whole:
            raise Failed(f"{name}: a chip holds {max(in_use)} bytes after "
                         f"set-up, not below one model copy ({whole})")

    batch = sess.data.next()
    _, tokens, labels = batch
    boundary = drv.boundary_at(0)
    want_fwd, want_train = plain_losses(jax, cfg, drv, tokens[0], labels[0],
                                        boundary)

    seen, all_losses = [], []
    for r in range(rounds):
        t0 = time.perf_counter()
        m = sess.step(batch if r == 0 else None).materialize()
        dt = time.perf_counter() - t0
        mode, losses = m.extras["mode"], m.extras["losses"]
        seen.append(mode)
        all_losses += losses
        print(f"[{name}] round {r}: backend={backend} S={n_stages} "
              f"boundary={m.boundary} mode={mode} owner_losses={losses} "
              f"smoke_seconds={dt:.2f}", flush=True)
        if r == 0:
            first = losses[0]
            if m.boundary != boundary:
                raise Failed(f"{name}: round 0 ran at boundary {m.boundary}, "
                             f"the references at {boundary}")
    check_finite(name, all_losses)
    if modes is not None and seen != modes:
        raise Failed(f"{name}: modes {seen}, expected {modes}")
    check_close(name, first, [("plain trained forward", want_train, tols[0]),
                              ("plain forward", want_fwd, tols[1])])
    print(f"[{name}] compile_seconds={clock.secs - c0:.1f} "
          f"peak_bytes_in_use per chip (GiB, process so far) "
          f"{[_gib(b) for b in device_bytes(jax, 'peak_bytes_in_use')]}",
          flush=True)


def run_pjit(jax, clock, name: str, cfg, tc, *, steps: int) -> None:
    from repro.api import RingSession
    from repro.models import transformer as tfm
    from repro.models.losses import cross_entropy

    c0 = clock.secs
    sess = RingSession.create(cfg, tc, backend="pjit")
    print(f"[{name}] set-up: backend=pjit S=1 bytes_in_use per chip (GiB) "
          f"{[_gib(b) for b in device_bytes(jax, 'bytes_in_use')]}",
          flush=True)
    batch = sess.data.next()
    want = float(jax.jit(
        lambda p, t, lab: cross_entropy(tfm.forward(p, t, cfg)[0], lab)[0])(
            sess.backend.export_params(), batch["tokens"], batch["labels"]))

    losses = []
    for i in range(steps):
        t0 = time.perf_counter()
        m = sess.step(batch if i == 0 else None).materialize()
        dt = time.perf_counter() - t0
        losses.append(m.loss)
        print(f"[{name}] step {i}: backend=pjit S=1 boundary={m.boundary} "
              f"mode=direct loss={m.loss!r} smoke_seconds={dt:.2f}",
              flush=True)
        if m.boundary != cfg.repeats - 1:
            raise Failed(f"{name}: boundary {m.boundary}, expected "
                         f"{cfg.repeats - 1}")
    check_finite(name, losses)
    check_close(name, losses[0], [("plain forward", want, PJIT_LOSS_TOL)])
    print(f"[{name}] compile_seconds={clock.secs - c0:.1f} "
          f"peak_bytes_in_use per chip (GiB, process so far) "
          f"{[_gib(b) for b in device_bytes(jax, 'peak_bytes_in_use')]}",
          flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases (a) and (b); 4: the four-chip ring only")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and data")
    args = ap.parse_args()

    cache_dir = use_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 1

    from repro.configs import TrainConfig, get_config

    cfg = get_config("stablelm-3b")
    clock = CompileClock(jax)
    print(f"device: {devices[0].device_kind} x{len(devices)}; model "
          f"{cfg.name}: {cfg.n_layers} blocks, d_model {cfg.d_model}, "
          f"{cfg.n_heads} heads, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
          f"{cfg.dtype}, {_gib(model_bytes(cfg))} GiB of weights; "
          f"compile cache {cache_dir}", flush=True)

    # depth 1 for every round: the paper's starting point (boundary 31)
    depth1 = dict(initial_unfreeze_depth=1, unfreeze_interval=10_000,
                  seed=args.seed)
    f32 = dataclasses.replace(cfg, n_layers=F32_BLOCKS, repeats=F32_BLOCKS,
                              dtype="float32")
    try:
        if args.chips == 4:
            tc = TrainConfig(batch_size=1, seq_len=1024, n_microbatches=4,
                             **depth1)
            ring = dict(backend="cached", n_stages=4, slots_per_epoch=2)
            run_ring(jax, clock, "c: cached ring S=4", cfg, tc, rounds=3,
                     modes=["capture", "capture", "cached"], **ring)
            gc.collect()
            with jax.default_matmul_precision("highest"):
                run_ring(jax, clock, "c32: cached ring S=4, f32, 4 blocks",
                         f32, tc, rounds=1, modes=["capture"],
                         tols=(F32_LOSS_TOL,) * 2, **ring)
        else:
            tc = TrainConfig(batch_size=1, seq_len=2048, n_microbatches=1,
                             **depth1)
            ring = dict(backend="fused", n_stages=1)
            run_ring(jax, clock, "a: fused ring S=1", cfg, tc, rounds=3,
                     modes=["direct"] * 3, **ring)
            gc.collect()                   # phase (a)'s buffers leave the chip
            with jax.default_matmul_precision("highest"):
                run_ring(jax, clock, "a32: fused ring S=1, f32, 4 blocks",
                         f32, tc, rounds=1, modes=["direct"],
                         tols=(F32_LOSS_TOL,) * 2, **ring)
            gc.collect()
            tc = TrainConfig(batch_size=8, seq_len=1024, **depth1)
            run_pjit(jax, clock, "b: pjit baseline", cfg, tc, steps=3)
    except Failed as e:
        print(f"chip_smoke: FAILED {e}", file=sys.stderr)
        return 1

    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {"platform": d.platform,
                                             "kind": d.device_kind,
                                             "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
