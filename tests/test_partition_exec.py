"""Heterogeneous ring execution: `assign_layers` partitions run for REAL.

The paper's coordinator assigns *uneven* contiguous block spans to
heterogeneous edge devices (Algorithm 1; the 4:5:2:3 example).  This module
is the differential harness between the three places that model/execute a
span layout:

  (a) closed forms   — ``pipeline.pipeline_tick_counts(spans=...)``,
  (b) the simulator  — ``simulator.spmd_tick_round`` (discrete-event engine
      in the SPMD executor's tick units),
  (c) the executor   — ``RingExecutor.measured_tick_ledger`` (the scan
      lengths XLA actually traced into the round executables),

plus the numerics contracts of heterogeneous execution:

  (d) loss/param equivalence — any span layout realizes the SAME function
      per microbatch (stages apply the same blocks in the same order), so
      ragged fused/cached/packed executors must match the uniform-partition
      oracle at the established 1e-5 / 1e-3 pins whenever the layouts share
      the aligned unfreeze boundary,
  (e) the partitioner itself — coverage, contiguity, memory feasibility and
      bottleneck-optimality vs brute force (deterministic; the hypothesis
      versions live in tests/test_property.py),
  (f) repartitioning — ``RingExecutor.repartition`` preserves numerics and
      flushes the activation cache (span-layout invalidation).
"""
import itertools
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.partition import (DeviceProfile, align_boundary,
                                  assign_layers, frozen_stage_count,
                                  normalize_spans, parse_device_profiles,
                                  span_boundaries, span_sizes,
                                  spans_from_profiles, uniform_assignment)
from repro.core.pipeline import pipeline_tick_counts
from repro.core.simulator import spmd_tick_round

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_sub(code: str) -> dict:
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


# ---------------------------------------------------------------------------
# (e) partitioner: layout helpers + uniform fallback
# ---------------------------------------------------------------------------


def test_uniform_assignment_divisible_unchanged():
    assert uniform_assignment(12, 4) == [(0, 3), (3, 6), (6, 9), (9, 12)]


def test_uniform_assignment_ragged_fallback():
    """n_blocks % n_stages != 0 no longer crashes: most balanced split,
    larger spans first, still a contiguous cover."""
    assert uniform_assignment(14, 4) == [(0, 4), (4, 8), (8, 11), (11, 14)]
    assert uniform_assignment(5, 2) == [(0, 3), (3, 5)]
    assert uniform_assignment(7, 7) == [(i, i + 1) for i in range(7)]
    for n, u in ((9, 4), (13, 3), (17, 5)):
        spans = uniform_assignment(n, u)
        sizes = span_sizes(spans)
        assert spans[0][0] == 0 and spans[-1][1] == n
        assert max(sizes) - min(sizes) <= 1          # most balanced
        assert sorted(sizes, reverse=True) == list(sizes)


def test_normalize_spans_sizes_and_pairs():
    want = ((0, 4), (4, 9), (9, 11), (11, 14))
    assert normalize_spans([4, 5, 2, 3]) == want
    assert normalize_spans(want, 14) == want
    with pytest.raises(ValueError, match="contiguous"):
        normalize_spans([(0, 4), (5, 9)])            # gap
    with pytest.raises(ValueError, match="contiguous"):
        normalize_spans([(0, 4), (2, 9)])            # overlap
    with pytest.raises(ValueError, match="contiguous"):
        normalize_spans([(0, 4), (4, 4)])            # empty span
    with pytest.raises(ValueError, match="covers"):
        normalize_spans([4, 5, 2, 3], 15)            # wrong model size


def test_align_boundary_and_frozen_count():
    sp = normalize_spans([4, 5, 2, 3])
    assert span_boundaries(sp) == (0, 4, 9, 11, 14)
    for raw, aligned, f in ((0, 0, 0), (3, 0, 0), (4, 4, 1), (8, 4, 1),
                            (9, 9, 2), (10, 9, 2), (11, 11, 3), (13, 11, 3)):
        assert align_boundary(sp, raw) == aligned
        assert frozen_stage_count(sp, aligned) == f
    with pytest.raises(ValueError, match="not span-aligned"):
        frozen_stage_count(sp, 5)


def test_assign_layers_paper_example():
    """Speeds skewed as 1.0 : 1.25 : 0.5 : 0.75 over 14 uniform blocks give
    the paper's 4:5:2:3 assignment (speed-proportional spans)."""
    profiles = parse_device_profiles([1.0, 1.25, 0.5, 0.75])
    assert span_sizes(spans_from_profiles(14, profiles)) == (4, 5, 2, 3)


# -- brute-force optimality ---------------------------------------------------


def _brute_force_bottleneck(costs, mems, devs):
    """Min bottleneck over ALL contiguous partitions that fit memory."""
    n, u = len(costs), len(devs)
    best = None
    for cuts in itertools.combinations(range(1, n), u - 1):
        edges = (0,) + cuts + (n,)
        t = 0.0
        ok = True
        for i, dev in enumerate(devs):
            a, b = edges[i], edges[i + 1]
            if sum(mems[a:b]) > dev.memory_mb:
                ok = False
                break
            t = max(t, sum(costs[a:b]) / dev.compute_speed)
        if ok and (best is None or t < best):
            best = t
    return best


@pytest.mark.parametrize("seed", range(8))
def test_assign_layers_bottleneck_optimal_vs_brute_force(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(4, 10))
    u = int(rng.integers(2, min(n, 4) + 1))
    costs = rng.uniform(0.2, 2.0, n).tolist()
    mems = rng.uniform(0.5, 2.0, n).tolist()
    devs = [DeviceProfile(compute_speed=float(rng.uniform(0.3, 2.0)),
                          memory_mb=float(rng.uniform(2.5, 8.0)))
            for _ in range(u)]
    want = _brute_force_bottleneck(costs, mems, devs)
    if want is None:
        with pytest.raises(ValueError, match="memory"):
            assign_layers(costs, mems, devs)
        return
    spans = assign_layers(costs, mems, devs)
    # coverage + contiguity + memory feasibility
    assert normalize_spans(spans, n) == tuple(spans)
    for (a, b), dev in zip(spans, devs):
        assert sum(mems[a:b]) <= dev.memory_mb + 1e-12
    got = max(sum(costs[a:b]) / dev.compute_speed
              for (a, b), dev in zip(spans, devs))
    assert got <= want * (1 + 1e-9) + 1e-12, (spans, got, want)


def test_assign_layers_memory_forces_smaller_spans():
    """A fast device with a tiny memory budget cannot hog blocks: memory
    caps its span even though speed alone would give it everything."""
    costs, mems = [1.0] * 6, [1.0] * 6
    fast_small = DeviceProfile(compute_speed=100.0, memory_mb=2.0)
    slow_big = DeviceProfile(compute_speed=1.0, memory_mb=100.0)
    spans = assign_layers(costs, mems, [fast_small, slow_big])
    assert span_sizes(spans)[0] == 2                 # memory-capped
    with pytest.raises(ValueError, match="memory"):
        assign_layers(costs, mems,
                      [DeviceProfile(1.0, 2.0), DeviceProfile(1.0, 2.0)])


# ---------------------------------------------------------------------------
# (a) vs (b): closed forms vs the discrete-event engine, uneven spans
# ---------------------------------------------------------------------------

LAYOUT_GRID = ([4, 5, 2, 3], [1, 1, 1, 1], [2, 1], [3, 1, 1, 2],
               [5, 1, 1, 1], [1, 6, 4, 3])


@pytest.mark.parametrize("layout", LAYOUT_GRID,
                         ids=[":".join(map(str, l)) for l in LAYOUT_GRID])
def test_sim_ticks_match_closed_forms_uneven_spans(layout):
    """The engine's makespan in SPMD tick units equals
    ``pipeline_tick_counts(spans=...)`` for every alignable boundary with a
    terminator, scanned and packed, across microbatch counts."""
    sp = normalize_spans(layout)
    S = len(sp)
    for boundary in span_boundaries(sp)[:-1]:        # F < S
        for M in (1, 2, 4):
            for packed in (False, True):
                want = pipeline_tick_counts(S, M, boundary=boundary,
                                            spans=sp, packed=packed)
                got = spmd_tick_round(sp, M, boundary, packed=packed)
                assert got["phase_a_round_ticks"] == \
                    want["phase_a_round_ticks"], (layout, boundary, M, packed)
                assert got["frozen_stages"] == want["frozen_stages"]
            cached = spmd_tick_round(sp, M, boundary, cached=True)
            assert cached["phase_a_round_ticks"] == 0


def test_span_tick_counts_equal_lps_form_when_uniform():
    for S, M, lps in ((4, 8, 3), (2, 4, 2), (4, 1, 1)):
        sp = [lps] * S
        for f in range(S):
            for kw in ({}, {"packed": True}, {"cached": True}):
                assert pipeline_tick_counts(S, M, boundary=f * lps,
                                            lps=lps, **kw) == \
                    pipeline_tick_counts(S, M, boundary=f * lps,
                                         spans=sp, **kw)


# ---------------------------------------------------------------------------
# (c) + (d): executor differential — 4-device subprocess
# ---------------------------------------------------------------------------

PRELUDE = """
import json
import jax, jax.numpy as jnp
from repro.core.pipeline import make_ring_mesh
from repro.configs import TrainConfig, get_config
from repro.models import params as P
from repro.core.executor import RingExecutor
from repro.core.ring import RingTrainer
from repro.core.pipeline import pipeline_tick_counts
from repro.core.simulator import spmd_tick_round

cfg = get_config("stablelm-3b").reduced(n_layers=14, repeats=14,
                                        d_model=64, d_ff=128, vocab_size=128)
S, M, mb, seq = 4, 2, 1, 16

def fresh_params():
    params = P.materialize(P.param_defs(cfg), jax.random.key(0))
    ad = params["blocks"][0]["adapter"]
    ad["w_up"] = 0.02 * jax.random.normal(jax.random.key(9), ad["w_up"].shape,
                                          jnp.float32).astype(ad["w_up"].dtype)
    return params

mesh = make_ring_mesh(S)

def batch(k=0):
    t = jax.random.randint(jax.random.key(10 + k), (S, M, mb, seq), 0,
                           cfg.vocab_size)
    l = jax.random.randint(jax.random.key(20 + k), (S, M, mb, seq), 0,
                           cfg.vocab_size)
    return t, l

f32 = lambda x: x.astype(jnp.float32)
maxerr = lambda a, b: max(jax.tree.leaves(jax.tree.map(
    lambda x, y: float(jnp.abs(f32(x) - f32(y)).max()), a, b)))
"""


def test_hetero_executor_matches_uniform_oracle_and_tick_ledger():
    """The headline acceptance test: 4:5:2:3 (and friends) train end-to-end
    on the 4-device mesh.

    All layouts share aligned boundary 11 (depth 3), so they compute the
    SAME function: losses/params must match the balanced-layout fused oracle
    at 1e-5 / 1e-3 — for the plain ragged executor, the per-owner-scan
    (packed=False) variant, AND the cached (Phase-A-skip) variant.  Each
    executor's measured tick ledger (the scan lengths XLA actually traced)
    must equal the closed forms AND the discrete-event simulator exactly.
    """
    code = PRELUDE + """
tc = TrainConfig(learning_rate=1e-3, unfreeze_interval=10**6,
                 initial_unfreeze_depth=3, n_microbatches=M,
                 batch_size=mb, seq_len=seq)
batches = [batch(0), batch(1)]
out = {}
with jax.set_mesh(mesh):
    oracle = RingExecutor(cfg, tc, mesh, fresh_params(), S, M)  # 4:4:3:3
    o_losses = []
    for r in range(4):
        t, l = batches[r % 2]
        o_losses.append(
            RingExecutor.materialize_metrics(oracle.round(t, l))["loss"])
    op = oracle.export_params()
    out["oracle_boundary"] = oracle.boundary_at(0)
    for name, kw in (
            ("4:5:2:3", dict(spans=[4, 5, 2, 3])),
            ("4:5:2:3/scan", dict(spans=[4, 5, 2, 3], packed=False)),
            ("2:4:5:3", dict(spans=[2, 4, 5, 3])),
            ("4:5:2:3/cached", dict(spans=[4, 5, 2, 3], cache_capacity=2)),
    ):
        cap = kw.get("cache_capacity", 0)
        drv = RingExecutor(cfg, tc, mesh, fresh_params(), S, M, **kw)
        losses, hits = [], []
        for r in range(4):
            t, l = batches[r % 2]
            m = RingExecutor.materialize_metrics(
                drv.round(t, l, slot=r % 2 if cap else None))
            losses.append(m["loss"])
            hits.append(m.get("cache_hit", False))
        b = drv.boundary_at(0)
        mode = "cached" if cap else "direct"
        led = drv.measured_tick_ledger(b, mode)
        packed_eff = (drv.packed and mode != "cached"
                      and led["frozen_stages"] >= 2)
        want = pipeline_tick_counts(S, M, boundary=b, spans=drv.spans,
                                    packed=packed_eff, cached=mode == "cached")
        sim = spmd_tick_round(drv.spans, M, b, packed=packed_eff,
                              cached=mode == "cached")
        out[name] = {
            "b": b, "losses": losses, "hits": hits,
            "param_err": maxerr(op, drv.export_params()),
            "loss_err": max(abs(a - c) for a, c in zip(o_losses, losses)),
            "ledger": led, "closed": want,
            "sim_phase_a": sim["phase_a_round_ticks"],
            "capture_ledger": (drv.measured_tick_ledger(b, "capture")
                               if cap else None),
        }
print(json.dumps(out))
"""
    res = _run_sub(code)
    assert res.pop("oracle_boundary") == 11
    for name, rec in res.items():
        # (d) same function as the uniform oracle: established pins hold
        assert rec["b"] == 11, (name, rec)
        assert rec["loss_err"] < 1e-5, (name, rec)
        assert rec["param_err"] < 1e-3, (name, rec)
        # (c) measured scan lengths == closed forms == discrete-event engine
        led, want = rec["ledger"], rec["closed"]
        assert led == want, (name, led, want)
        assert led["phase_a_round_ticks"] == rec["sim_phase_a"], (name, rec)
        if name.endswith("/cached"):
            assert rec["hits"] == [False, False, True, True], (name, rec)
            assert led["phase_a_round_ticks"] == 0
            # the capture executable still pays full Phase A
            cap = rec["capture_ledger"]
            assert cap["phase_a_round_ticks"] > 0, (name, cap)


def test_hetero_boundary_walk_fused_vs_reference():
    """Walking the unfreeze schedule on a ragged layout: the fused executor
    and the unfused RingTrainer oracle align boundaries identically
    (span edges, not lps multiples) and stay loss/param-equivalent."""
    code = PRELUDE + """
tc = TrainConfig(learning_rate=1e-3, unfreeze_interval=2 * S,
                 initial_unfreeze_depth=3, n_microbatches=M,
                 batch_size=mb, seq_len=seq)
spans = [4, 5, 2, 3]
tokens, labels = batch(0)
out = {"fused": [], "ref": [], "b": []}
with jax.set_mesh(mesh):
    fused = RingExecutor(cfg, tc, mesh, fresh_params(), S, M, spans=spans)
    ref = RingTrainer(cfg, tc, mesh, fresh_params(), S, M, spans=spans)
    for r in range(6):
        mf = RingExecutor.materialize_metrics(fused.round(tokens, labels))
        mr = ref.round(tokens, labels)
        out["fused"].append(mf["loss"])
        out["ref"].append(mr["loss"])
        assert mf["boundary"] == mr["boundary"], (mf, mr)
        out["b"].append(mf["boundary"])
    out["param_err"] = maxerr(fused.export_params(), ref.export_params())
print(json.dumps(out))
"""
    res = _run_sub(code)
    # depth 3 -> b=11 aligned; depth walks 3,4,5,6,... -> raw 11,10,9,8 ->
    # aligned 11,9,9,4 at rounds (interval = 2 rounds)
    assert res["b"][0] == 11 and res["b"][-1] < 11
    assert sorted(res["b"], reverse=True) == res["b"]      # monotone drop
    for fl, rl in zip(res["fused"], res["ref"]):
        assert abs(fl - rl) < 1e-5, res
    assert res["param_err"] < 1e-3


def test_repartition_preserves_numerics_and_flushes_cache():
    """(f): mid-run repartition balanced -> 4:5:2:3 keeps training
    loss-identical to a never-repartitioned uncached oracle (params + Adam
    moments restack exactly), while the activation cache does a whole-cache
    span-layout invalidation and re-captures."""
    code = PRELUDE + """
tc = TrainConfig(learning_rate=1e-3, unfreeze_interval=10**6,
                 initial_unfreeze_depth=3, n_microbatches=M,
                 batch_size=mb, seq_len=seq)
batches = [batch(0), batch(1)]
out = {"plain": [], "repart": [], "hits": []}
with jax.set_mesh(mesh):
    plain = RingExecutor(cfg, tc, mesh, fresh_params(), S, M)
    drv = RingExecutor(cfg, tc, mesh, fresh_params(), S, M, cache_capacity=2)
    for r in range(8):
        if r == 4:
            drv.repartition([4, 5, 2, 3])
            out["layout_inval"] = drv.cache.invalidations
        t, l = batches[r % 2]
        mp = RingExecutor.materialize_metrics(plain.round(t, l))
        mc = RingExecutor.materialize_metrics(drv.round(t, l, slot=r % 2))
        out["plain"].append(mp["loss"])
        out["repart"].append(mc["loss"])
        out["hits"].append(mc["cache_hit"])
    out["param_err"] = maxerr(plain.export_params(), drv.export_params())
    out["stats"] = drv.cache.stats()
    out["spans"] = [list(sp) for sp in drv.spans]
print(json.dumps(out))
"""
    res = _run_sub(code)
    assert res["spans"] == [[0, 4], [4, 9], [9, 11], [11, 14]]
    # capture, capture, hit, hit -- repartition -- capture, capture, hit, hit
    assert res["hits"] == [False, False, True, True] * 2, res
    assert res["layout_inval"] == 1                      # span-layout flush
    for pl, rl in zip(res["plain"], res["repart"]):
        assert abs(pl - rl) < 1e-5, res
    assert res["param_err"] < 1e-3
    assert res["stats"]["cache_invalidations"] == 1


def test_session_hetero_checkpoint_roundtrip():
    """RingSession.create(device_profiles=...) derives the 4:5:2:3 layout,
    trains, saves; restore rebuilds the SAME spans from the checkpoint (no
    CLI flags needed) and continues with identical losses.  Restoring into a
    mismatched explicit layout fails the format check loudly."""
    code = PRELUDE + """
import os, tempfile
from repro.api import RingSession
tc = TrainConfig(learning_rate=1e-3, unfreeze_interval=10**6,
                 initial_unfreeze_depth=3, n_microbatches=M,
                 batch_size=mb, seq_len=seq)
path = os.path.join(tempfile.mkdtemp(), "het_ck")
sess = RingSession.create(cfg, tc, backend="fused", n_stages=S,
                          device_profiles=[1.0, 1.25, 0.5, 0.75])
spans0 = [list(sp) for sp in sess.backend.spans]
sess.run(2)
sess.save(path)
cont = [h["loss"] for h in sess.run(3)]
restored = RingSession.restore(path, cfg, tc)
again = [h["loss"] for h in restored.run(3)]
bad = None
try:
    RingSession.restore(path, cfg, tc, spans=[3, 4, 3, 4])
except ValueError as e:
    bad = str(e)
print(json.dumps({"spans0": spans0,
                  "spans1": [list(sp) for sp in restored.backend.spans],
                  "cont": cont, "again": again, "bad": bad}))
"""
    res = _run_sub(code)
    assert res["spans0"] == [[0, 4], [4, 9], [9, 11], [11, 14]]
    assert res["spans1"] == res["spans0"]          # layout rode the ckpt
    assert res["cont"] == res["again"], res
    assert res["bad"] and "format" in res["bad"], res
