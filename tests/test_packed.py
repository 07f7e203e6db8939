"""Packed-conveyor Phase A (+ compressed cache entries) vs the fused oracle.

Pins the tentpole contracts of the packed executor (core/pipeline.py
``ring_phase_a_packed`` + core/executor.py ``packed=True``):

  (a) equivalence — for every (S, M, boundary, Lps) in the grid, the packed
      executor's losses and exported params match the per-owner-scan fused
      oracle at the f32 pins (1e-5 / 1e-3), across a boundary walk (the
      conveyor is re-built per boundary; each microbatch sees the same op
      sequence as the scan, only the conveyor length differs),
  (b) cache interplay — capture -> cached transitions and boundary-drop
      invalidation behave identically under the packed conveyor, for every
      storage dtype: f32/bf16 entries stay at the 1e-5/1e-3 pins (lossless
      round-trips for a bf16 model), int8 at calibrated tolerances (per-row
      symmetric quantization, ~0.4% row error compounding over 8 rounds),
  (c) executable shape — packing changes the Phase-A *interior* of the
      direct/capture executables, not their count or the (boundary, mode)
      naming.
"""
import json
import os
import subprocess
import sys

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


PRELUDE = """
import json
import jax, jax.numpy as jnp
from repro.core.pipeline import make_ring_mesh
from repro.configs import TrainConfig, get_config
from repro.models import params as P
from repro.core.executor import RingExecutor

def fresh_params(cfg):
    params = P.materialize(P.param_defs(cfg), jax.random.key(0))
    ad = params["blocks"][0]["adapter"]
    ad["w_up"] = 0.02 * jax.random.normal(jax.random.key(9), ad["w_up"].shape,
                                          jnp.float32).astype(ad["w_up"].dtype)
    return params

def batch(cfg, S, M, mb, seq, k=0):
    t = jax.random.randint(jax.random.key(10 + k), (S, M, mb, seq), 0,
                           cfg.vocab_size)
    l = jax.random.randint(jax.random.key(20 + k), (S, M, mb, seq), 0,
                           cfg.vocab_size)
    return t, l

f32 = lambda x: x.astype(jnp.float32)
maxerr = lambda a, b: max(jax.tree.leaves(jax.tree.map(
    lambda x, y: float(jnp.abs(f32(x) - f32(y)).max()), a, b)))
"""


def test_packed_matches_scan_across_grid():
    """(a) + (c): three (S, M, Lps) geometries, each walking its boundary
    schedule (interval = S steps -> one drop per round), packed vs scan."""
    code = PRELUDE + """
out = {}
# (S, M, lps): 4 stages 1 block each; 2 stages 2 blocks each (stage-aligned
# boundary != block boundary); 4 stages with a single microbatch (conveyor
# degenerates to S + F - 1 ticks).
for S, M, lps in ((4, 3, 1), (2, 2, 2), (4, 1, 1)):
    cfg = get_config("stablelm-3b").reduced(n_layers=4, repeats=S * lps,
                                            d_model=128, d_ff=256)
    mb, seq = 1, 32
    tc = TrainConfig(learning_rate=1e-3, unfreeze_interval=S, n_microbatches=M,
                     batch_size=mb, seq_len=seq)
    mesh = make_ring_mesh(S, devices=jax.devices()[:S])
    tokens, labels = batch(cfg, S, M, mb, seq)
    rec = {"scan_loss": [], "packed_loss": [], "b": []}
    with jax.set_mesh(mesh):
        scan = RingExecutor(cfg, tc, mesh, fresh_params(cfg), S, M,
                            packed=False)
        pk = RingExecutor(cfg, tc, mesh, fresh_params(cfg), S, M, packed=True)
        for r in range(3):
            ms = RingExecutor.materialize_metrics(scan.round(tokens, labels))
            mp = RingExecutor.materialize_metrics(pk.round(tokens, labels))
            rec["scan_loss"].append(ms["loss"])
            rec["packed_loss"].append(mp["loss"])
            assert ms["boundary"] == mp["boundary"]
            rec["b"].append(mp["boundary"])
        rec["param_err"] = maxerr(scan.export_params(), pk.export_params())
        rec["packed_compiles"] = pk.compile_counts()
        rec["scan_compiles"] = scan.compile_counts()
    out[f"S{S}_M{M}_lps{lps}"] = rec
print(json.dumps(out))
"""
    res = _run_sub(code)
    for name, rec in res.items():
        for sl, pl in zip(rec["scan_loss"], rec["packed_loss"]):
            assert abs(sl - pl) < 1e-5, (name, rec)
        assert rec["param_err"] < 1e-3, (name, rec)
        # (c) same executable set, same naming — packing is interior-only
        assert rec["packed_compiles"] == rec["scan_compiles"], (name, rec)
        assert all(k.endswith("/direct") for k in rec["packed_compiles"])


def test_packed_cache_dtypes_across_boundary_drop():
    """(b): packed capture -> cached transitions + boundary-drop invalidation
    per storage dtype, all against the scan-Phase-A uncached oracle.

    2 slots x 8 rounds, boundary dropping once mid-run (interval = 4 rounds'
    steps => capture, capture, hit, hit per boundary).  f32/bf16 round-trip a
    bf16 model's activations losslessly -> the 1e-5/1e-3 pins hold; int8 is
    pinned at calibrated tolerances (loss 8e-2 / params 2e-1, ~2x the drift
    measured on this grid) plus a sanity floor that it still tracks."""
    code = PRELUDE + """
S, M, mb, seq = 4, 3, 1, 32
cfg = get_config("stablelm-3b").reduced(n_layers=4, repeats=4,
                                        d_model=128, d_ff=256)
tc = TrainConfig(learning_rate=1e-3, unfreeze_interval=4 * S, n_microbatches=M,
                 batch_size=mb, seq_len=seq)
mesh = make_ring_mesh(4)
batches = [batch(cfg, S, M, mb, seq, k=0), batch(cfg, S, M, mb, seq, k=1)]
out = {}
with jax.set_mesh(mesh):
    plain = RingExecutor(cfg, tc, mesh, fresh_params(cfg), S, M, packed=False)
    plain_loss = []
    for r in range(8):
        t, l = batches[r % 2]
        plain_loss.append(
            RingExecutor.materialize_metrics(plain.round(t, l))["loss"])
    pp = plain.export_params()
    for dt in ("f32", "bf16", "int8"):
        drv = RingExecutor(cfg, tc, mesh, fresh_params(cfg), S, M,
                           cache_capacity=2, cache_dtype=dt, packed=True)
        losses, hits, bounds = [], [], []
        for r in range(8):
            t, l = batches[r % 2]
            m = RingExecutor.materialize_metrics(drv.round(t, l, slot=r % 2))
            losses.append(m["loss"])
            hits.append(m["cache_hit"])
            bounds.append(m["boundary"])
        st = drv.cache.stats()
        out[dt] = {
            "max_loss_err": max(abs(a - b)
                                for a, b in zip(plain_loss, losses)),
            "param_err": maxerr(pp, drv.export_params()),
            "hits": hits, "bounds": bounds,
            "stats": {k: st[k] for k in
                      ("cache_hits", "cache_misses", "cache_invalidations",
                       "cache_bypasses", "cache_dtype",
                       "cache_bytes_per_entry")},
            "compiles": drv.compile_counts(),
        }
print(json.dumps(out))
"""
    res = _run_sub(code)
    tol = {"f32": (1e-5, 1e-3), "bf16": (1e-5, 1e-3), "int8": (8e-2, 2e-1)}
    f32_bytes = res["f32"]["stats"]["cache_bytes_per_entry"]
    for dt, rec in res.items():
        lt, pt = tol[dt]
        assert rec["max_loss_err"] < lt, (dt, rec)
        assert rec["param_err"] < pt, (dt, rec)
        # cache behavior is dtype-independent: capture, capture, hit, hit
        # around the drop, one invalidation, no bypasses
        assert rec["hits"] == [False, False, True, True] * 2, (dt, rec)
        assert rec["bounds"] == [3] * 4 + [2] * 4, (dt, rec)
        st = rec["stats"]
        assert st["cache_hits"] == 4 and st["cache_misses"] == 4
        assert st["cache_invalidations"] == 1 and st["cache_bypasses"] == 0
        assert st["cache_dtype"] == dt
        # one capture + one cached executable per boundary, packed or not
        assert rec["compiles"] == {f"{b}/{m}": 1 for b in (3, 2)
                                   for m in ("capture", "cached")}, (dt, rec)
    # the compression claim: bf16 halves, int8 ~quarters the bytes per entry
    assert res["bf16"]["stats"]["cache_bytes_per_entry"] * 2 == f32_bytes
    assert res["int8"]["stats"]["cache_bytes_per_entry"] < 0.3 * f32_bytes
    # int8 still *tracks* (sanity floor: not garbage)
    assert res["int8"]["max_loss_err"] > 0  # lossy, so not bit-equal
