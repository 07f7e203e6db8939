"""Frozen-trunk activation cache (core/actcache.py + executor cached mode).

Pins the cache's contracts:

  (a) equivalence — with epoch-stable batch slots, the cached executor's
      losses and exported params match the cache-disabled fused executor
      exactly, INCLUDING across boundary drops (where the cache must
      invalidate and re-capture, not serve stale trunk activations),
  (b) accounting — hits/misses/invalidations/evictions/bypasses count what
      actually happened; slot=None and shape-mismatched batches fall back to
      the direct path,
  (c) compile counts — capture + cached are one executable each per boundary
      (the cached one has no Phase A at all: its HLO takes no tokens),
  (d) the ring-buffer host logic (LRU, invalidate, donated writes) in
      isolation on one device.
"""
import json
import os
import subprocess
import sys

import jax.numpy as jnp

from repro.core.actcache import ActivationCache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_sub(code: str) -> dict:
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


# ---------------------------------------------------------------------------
# (d) host-side ring-buffer logic, single device
# ---------------------------------------------------------------------------


def _entry(v, shape=(2, 3)):
    return jnp.full(shape, v, jnp.float32)


def test_cache_lru_eviction_and_reuse():
    c = ActivationCache(2)
    assert c.put(("s0", 3), _entry(1.0))
    assert c.put(("s1", 3), _entry(2.0))
    assert len(c) == 2
    # touch s0 so s1 becomes LRU, then insert s2 -> s1 evicted
    assert c.index_of(("s0", 3)) is not None
    assert c.put(("s2", 3), _entry(3.0))
    assert c.evictions == 1
    assert c.index_of(("s1", 3)) is None          # miss (evicted)
    i0, i2 = c.index_of(("s0", 3)), c.index_of(("s2", 3))
    assert i0 is not None and i2 is not None and i0 != i2
    assert float(c.buffer[i0][0, 0]) == 1.0       # survivor kept its bits
    assert float(c.buffer[i2][0, 0]) == 3.0       # evicted row was overwritten
    assert c.hits == 3 and c.misses == 1


def test_cache_put_overwrites_same_key():
    c = ActivationCache(2)
    c.put(("s0", 3), _entry(1.0))
    c.put(("s0", 3), _entry(9.0))
    assert len(c) == 1 and c.evictions == 0
    assert float(c.buffer[c.index_of(("s0", 3))][0, 0]) == 9.0


def test_cache_invalidate_keeps_buffer_counts_event():
    c = ActivationCache(2)
    c.put(("s0", 3), _entry(1.0))
    c.put(("s1", 3), _entry(2.0))
    assert c.invalidate() == 2
    assert c.invalidations == 1 and len(c) == 0
    assert c.invalidate() == 0                     # empty: no second event
    assert c.invalidations == 1
    # buffer survives (same shapes): re-capture reuses the allocation
    assert c.put(("s0", 2), _entry(5.0))
    assert float(c.buffer[c.index_of(("s0", 2))][0, 0]) == 5.0


def test_cache_shape_mismatch_bypasses():
    c = ActivationCache(2)
    c.put(("s0", 3), _entry(1.0))
    assert not c.compatible((4, 4))
    assert not c.put(("s1", 3), _entry(2.0, shape=(4, 4)))
    assert c.bypasses == 1 and len(c) == 1
    assert not c.compatible((2, 3), jnp.bfloat16)  # dtype checked when given
    assert c.compatible((2, 3), jnp.float32)


def test_cache_capacity_zero_disabled():
    c = ActivationCache(0)
    assert not c.compatible((2, 3))
    assert not c.put(("s0", 3), _entry(1.0))
    assert c.index_of(("s0", 3)) is None


def test_cache_free_rows_o1_and_consistent():
    """The free-row list replaces the O(capacity) first-free scan: rows stay
    unique, in range, and the free list + live rows always partition
    [0, capacity) — across fills, eviction, invalidation and refills."""
    c = ActivationCache(3)

    def check():
        live = list(c._rows.values())
        assert len(set(live)) == len(live)
        assert sorted(live + c._free) == list(range(3))

    for i in range(3):
        assert c.put((f"s{i}", 3), _entry(float(i)))
        check()
    assert c._free == []
    assert c.put(("s3", 3), _entry(3.0))          # evicts s0, reuses its row
    check()
    assert c.evictions == 1 and len(c) == 3
    c.invalidate()
    check()
    assert len(c._free) == 3
    for i in range(3):                            # refill reuses all rows
        assert c.put((f"t{i}", 2), _entry(10.0 + i))
        check()
    rows = {k: c.index_of(k) for k in (("t0", 2), ("t1", 2), ("t2", 2))}
    for k, r in rows.items():
        assert float(c.buffer[r][0, 0]) == 10.0 + int(k[0][1])


def test_cache_dtype_bf16_halves_bytes_roundtrip():
    c = ActivationCache(2, dtype="bf16")
    e = jnp.linspace(-3.0, 3.0, 6, dtype=jnp.float32).reshape(2, 3)
    assert c.put(("s0", 3), e)
    assert c.buffer.dtype == jnp.bfloat16
    assert c.scales is None
    from repro.core.actcache import dequantize
    back = dequantize(c.buffer[c.index_of(("s0", 3))], None, "bf16",
                      jnp.float32)
    assert float(jnp.abs(back - e).max()) < 0.05   # bf16 has ~3 digits
    # 2 bytes/elem vs f32's 4
    assert c.entry_bytes() == 2 * 6
    f = ActivationCache(2, dtype="f32")
    f.put(("s0", 3), e)
    assert f.entry_bytes() == 4 * 6


def test_cache_dtype_int8_scales_sidecar_roundtrip():
    c = ActivationCache(2, dtype="int8")
    e = jnp.linspace(-3.0, 3.0, 8, dtype=jnp.float32).reshape(2, 4)
    assert c.put(("s0", 3), e)
    assert c.buffer.dtype == jnp.int8
    assert c.scales is not None and c.scales.shape == (2, 2, 1)
    from repro.core.actcache import dequantize
    r = c.index_of(("s0", 3))
    back = dequantize(c.buffer[r], c.scales[r], "int8", jnp.float32)
    # symmetric per-row int8: error <= scale/2 = max|row| / 254
    row_max = jnp.max(jnp.abs(e), axis=-1, keepdims=True)
    assert bool((jnp.abs(back - e) <= row_max / 127.0).all())
    # 1 byte/elem + one f32 scale per 4-wide row
    assert c.entry_bytes() == 8 + 2 * 4
    st = c.stats()
    assert st["cache_dtype"] == "int8"
    assert st["cache_bytes_per_entry"] == 16
    assert st["cache_buffer_bytes"] == 32


def test_cache_source_dtype_still_guarded_under_compression():
    """compatible() checks the CAPTURED dtype, not the storage dtype — a
    bf16-compressed cache of f32 activations must still bypass bf16-source
    batches (they would silently dequantize to the wrong dtype)."""
    c = ActivationCache(2, dtype="bf16")
    c.put(("s0", 3), _entry(1.0))                  # f32 source
    assert c.compatible((2, 3), jnp.float32)
    assert not c.compatible((2, 3), jnp.bfloat16)
    assert not c.put(("s1", 3), _entry(2.0).astype(jnp.bfloat16))
    assert c.bypasses == 1


def test_cache_rejects_unknown_dtype():
    import pytest
    with pytest.raises(ValueError):
        ActivationCache(2, dtype="fp4")


def test_cache_span_layout_change_invalidates():
    """Entries are stage-local shards of a specific span layout: a
    repartition makes every held entry permanently wrong, so ``set_layout``
    must flush the whole cache (one invalidation event, like a boundary
    drop) while keeping the buffer allocation; the SAME layout is a no-op."""
    layout_a = ((0, 4), (4, 8), (8, 11), (11, 14))
    layout_b = ((0, 4), (4, 9), (9, 11), (11, 14))      # 4:5:2:3
    c = ActivationCache(2, layout=layout_a)
    assert c.layout == layout_a
    c.put(("s0", 11), _entry(1.0))
    c.put(("s1", 11), _entry(2.0))
    assert c.set_layout(layout_a) == 0                  # same layout: no-op
    assert len(c) == 2 and c.invalidations == 0
    assert c.set_layout(layout_b) == 2                  # repartition: flush
    assert c.layout == layout_b
    assert len(c) == 0 and c.invalidations == 1
    assert c.index_of(("s0", 11)) is None
    # buffer survives (same entry shapes): re-capture reuses the allocation
    assert c.put(("s0", 11), _entry(5.0))
    assert float(c.buffer[c.index_of(("s0", 11))][0, 0]) == 5.0
    # an empty cache still tracks the layout without a spurious event
    d = ActivationCache(2, layout=layout_a)
    assert d.set_layout(layout_b) == 0
    assert d.invalidations == 0 and d.layout == layout_b


def test_cache_shape_mismatch_bypasses_at_nonuniform_boundary():
    """Shape-mismatch bypass is orthogonal to the span layout: a ragged
    layout's cache still refuses (and counts) entries whose shapes don't fit
    the allocated buffer, at span-aligned (non-lps-multiple) boundaries."""
    c = ActivationCache(2, layout=((0, 4), (4, 9), (9, 11), (11, 14)))
    assert c.put(("s0", 9), _entry(1.0))                # boundary 9: 2 stages
    assert not c.compatible((4, 4))
    assert not c.put(("s1", 9), _entry(2.0, shape=(4, 4)))
    assert c.bypasses == 1 and len(c) == 1
    assert c.index_of(("s0", 9)) is not None            # survivor intact
    # a boundary key from another span edge shares the buffer fine
    assert c.put(("s0", 11), _entry(3.0))


# ---------------------------------------------------------------------------
# (a)+(b)+(c): cached executor vs cache-disabled fused executor, 4 devices
# ---------------------------------------------------------------------------

PRELUDE = """
import json
import jax, jax.numpy as jnp
from repro.core.pipeline import make_ring_mesh
from repro.configs import TrainConfig, get_config
from repro.models import params as P
from repro.core.executor import RingExecutor

cfg = get_config("stablelm-3b").reduced(n_layers=4, repeats=4,
                                        d_model=128, d_ff=256)
S, M, mb, seq = 4, 3, 1, 32

def fresh_params():
    params = P.materialize(P.param_defs(cfg), jax.random.key(0))
    ad = params["blocks"][0]["adapter"]
    ad["w_up"] = 0.02 * jax.random.normal(jax.random.key(9), ad["w_up"].shape,
                                          jnp.float32).astype(ad["w_up"].dtype)
    return params

mesh = make_ring_mesh(4)

def slot_batch(k, seq_=seq):
    t = jax.random.randint(jax.random.key(10 + k), (S, M, mb, seq_), 0,
                           cfg.vocab_size)
    l = jax.random.randint(jax.random.key(20 + k), (S, M, mb, seq_), 0,
                           cfg.vocab_size)
    return t, l

f32 = lambda x: x.astype(jnp.float32)
maxerr = lambda a, b: max(jax.tree.leaves(jax.tree.map(
    lambda x, y: float(jnp.abs(f32(x) - f32(y)).max()), a, b)))
"""


def test_cached_matches_uncached_across_boundary_drop():
    """(a)+(c): 2 slots x 6 rounds per driver, boundary walking 3 -> 2 -> 1
    (interval = 4 rounds' worth of steps => 2 epochs per boundary: capture,
    capture, hit, hit).  Losses and final params must match the cache-disabled
    executor, the cache must invalidate on each drop, and each boundary must
    compile exactly one capture + one cached executable."""
    code = PRELUDE + """
tc = TrainConfig(learning_rate=1e-3, unfreeze_interval=4 * S, n_microbatches=M,
                 batch_size=mb, seq_len=seq)
batches = [slot_batch(0), slot_batch(1)]
out = {"plain_loss": [], "cached_loss": [], "hit": [], "b": []}
with jax.set_mesh(mesh):
    plain = RingExecutor(cfg, tc, mesh, fresh_params(), S, M)
    drv = RingExecutor(cfg, tc, mesh, fresh_params(), S, M, cache_capacity=2)
    for r in range(12):
        slot = r % 2
        t, l = batches[slot]
        mp = RingExecutor.materialize_metrics(plain.round(t, l))
        mc = RingExecutor.materialize_metrics(drv.round(t, l, slot=slot))
        out["plain_loss"].append(mp["loss"])
        out["cached_loss"].append(mc["loss"])
        out["hit"].append(mc["cache_hit"])
        out["b"].append(mc["boundary"])
        assert mp["boundary"] == mc["boundary"]
    out["param_err"] = maxerr(plain.export_params(), drv.export_params())
    out["stats"] = drv.cache.stats()
    out["compiles"] = drv.compile_counts()
    out["plain_compiles"] = plain.compile_counts()
print(json.dumps(out))
"""
    res = _run_sub(code)
    assert res["b"] == [3] * 4 + [2] * 4 + [1] * 4
    # capture, capture, hit, hit at every boundary
    assert res["hit"] == [False, False, True, True] * 3
    # (a) cached == uncached, including the rounds right after each drop
    for pl, cl in zip(res["plain_loss"], res["cached_loss"]):
        assert abs(pl - cl) < 1e-5, (res["plain_loss"], res["cached_loss"])
    assert res["param_err"] < 1e-3
    st = res["stats"]
    assert st["cache_hits"] == 6 and st["cache_misses"] == 6
    assert st["cache_invalidations"] == 2          # drops 3->2 and 2->1
    assert st["cache_evictions"] == 0 and st["cache_bypasses"] == 0
    # (c) one capture + one cached executable per boundary, nothing else
    assert res["compiles"] == {f"{b}/{m}": 1 for b in (3, 2, 1)
                               for m in ("capture", "cached")}
    assert res["plain_compiles"] == {f"{b}/direct": 1 for b in (3, 2, 1)}


def test_cache_bypass_fallbacks():
    """(b): slot=None routes to the direct executable (no cache traffic);
    a batch whose shapes don't fit the allocated buffer bypasses; capacity-1
    thrashing evicts instead of hitting — and numerics survive all of it."""
    code = PRELUDE + """
tc = TrainConfig(learning_rate=1e-3, unfreeze_interval=10**6, n_microbatches=M,
                 batch_size=mb, seq_len=seq)
b0, b1 = slot_batch(0), slot_batch(1)
short = slot_batch(2, seq_=16)
out = {}
with jax.set_mesh(mesh):
    drv = RingExecutor(cfg, tc, mesh, fresh_params(), S, M, cache_capacity=1)
    drv.round(*b0, slot=None)                 # streaming round: direct path
    out["after_none"] = drv.cache.stats()
    drv.round(*b0, slot=0)                    # capture slot 0
    drv.round(*b1, slot=1)                    # capacity 1 -> evicts slot 0
    drv.round(*b0, slot=0)                    # miss again (was evicted)
    out["after_thrash"] = drv.cache.stats()
    drv.round(*short, slot=3)                 # doesn't fit allocated buffer
    out["after_short"] = drv.cache.stats()
    drv.round(*b0, slot=0)                    # still works, still a hit
    out["final"] = drv.cache.stats()
    out["compiles"] = drv.compile_counts()
print(json.dumps(out))
"""
    res = _run_sub(code)
    a = res["after_none"]
    assert a["cache_hits"] == 0 and a["cache_misses"] == 0, a
    t = res["after_thrash"]
    assert t["cache_misses"] == 3 and t["cache_evictions"] == 2
    s = res["after_short"]
    assert s["cache_bypasses"] == 1
    assert s["cache_misses"] == 3                  # bypass is not a miss
    f = res["final"]
    assert f["cache_hits"] == 1
    comp = res["compiles"]
    # direct compiled twice: once for slot=None, once for the short batch's
    # distinct shapes; capture once; cached once (first actual hit)
    assert comp["3/capture"] == 1 and comp["3/cached"] == 1
    assert comp["3/direct"] == 2, comp
