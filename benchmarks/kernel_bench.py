"""Kernel benchmarks: Pallas (interpret) vs jnp reference — correctness +
analytic roofline terms for the TPU target (no TPU wall-clock on CPU)."""
from __future__ import annotations

import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref
from repro.roofline import HBM_BW, PEAK_FLOPS


def _t(fn, *args, iters=3):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def run(log=print) -> Dict:
    out = {}

    # --- adapter fusion: arithmetic-intensity analysis -----------------------
    T, D, m = 4096, 2560, 64
    h = jax.random.normal(jax.random.key(0), (T, D), jnp.bfloat16)
    wd = 0.05 * jax.random.normal(jax.random.key(1), (D, m), jnp.float32)
    wu = 0.05 * jax.random.normal(jax.random.key(2), (m, D), jnp.float32)
    flops = 4 * T * D * m
    bytes_unfused = (3 * T * D + 2 * T * m + 2 * D * m) * 2   # 3x h streams
    bytes_fused = (2 * T * D + 2 * D * m) * 2                  # h in + out
    jref = jax.jit(lambda *a: ref.adapter_fused(*a))
    t_ref = _t(jref, h, wd, wu)
    got = ops.adapter_fused(h, wd, wu)
    err = float(jnp.abs(got.astype(jnp.float32)
                        - jref(h, wd, wu).astype(jnp.float32)).max())
    out["adapter_fused"] = {
        "shape": f"T{T}xD{D}xm{m}", "max_err": err,
        "jnp_cpu_us": t_ref * 1e6,
        "tpu_mem_term_unfused_us": bytes_unfused / HBM_BW * 1e6,
        "tpu_mem_term_fused_us": bytes_fused / HBM_BW * 1e6,
        "tpu_compute_term_us": flops / PEAK_FLOPS * 1e6,
        "fusion_speedup_bound": bytes_unfused / bytes_fused,
    }
    log(f"  adapter_fused err={err:.4f} "
        f"mem-bound speedup bound={bytes_unfused/bytes_fused:.2f}x")

    # --- rwkv chunked scan: flops vs sequential ------------------------------
    N, S, hd, L = 8, 512, 64, 32
    keys = jax.random.split(jax.random.key(3), 6)
    r, k, v = (jax.random.normal(keys[i], (N, S, hd), jnp.float32)
               for i in range(3))
    lw = -jnp.exp(0.5 * jax.random.normal(keys[3], (N, S, hd)) - 1.0)
    u = 0.5 * jax.random.normal(keys[4], (N, 1, hd))
    s0 = jnp.zeros((N, hd, hd))
    jr = jax.jit(lambda *a: ref.rwkv_scan(*a))
    t_seq = _t(jr, r, k, v, lw, u, s0)
    o1, s1 = ops.rwkv_scan(r, k, v, lw, u, s0)
    o2, s2 = jr(r, k, v, lw, u, s0)
    err = float(jnp.abs(o1 - o2).max())
    # chunked kernel: matmul flops per chunk ~ 3*L^2*hd + 2*L*hd^2
    chunk_flops = (S // L) * (3 * L * L * hd + 4 * L * hd * hd) * N
    out["rwkv_scan"] = {
        "shape": f"N{N}xS{S}xhd{hd}", "max_err": err,
        "seq_scan_cpu_us": t_seq * 1e6,
        "chunked_tpu_compute_us": chunk_flops / PEAK_FLOPS * 1e6,
        "hbm_roundtrips_seq": S, "hbm_roundtrips_chunked": S // L,
    }
    log(f"  rwkv_scan err={err:.5f} HBM roundtrips {S} -> {S // L}")

    # --- flash attention: memory traffic bound -------------------------------
    Nq, Sq, hd2, g = 8, 1024, 128, 4
    q = jax.random.normal(jax.random.key(5), (1, Nq, Sq, hd2), jnp.bfloat16)
    kk = jax.random.normal(jax.random.key(6), (1, Nq // g, Sq, hd2),
                           jnp.bfloat16)
    vv = jax.random.normal(jax.random.key(7), (1, Nq // g, Sq, hd2),
                           jnp.bfloat16)
    got = ops.flash_attention(q, kk, vv)
    want = ref.flash_attention(q, kk, vv)
    err = float(jnp.abs(got.astype(jnp.float32)
                        - want.astype(jnp.float32)).max())
    bytes_naive = (Nq * Sq * Sq * 2) * 2 + 3 * Nq * Sq * hd2 * 2  # probs to HBM
    bytes_flash = (3 * Nq * Sq * hd2 + Nq * Sq * hd2) * 2
    out["flash_attention"] = {
        "shape": f"N{Nq}xS{Sq}xhd{hd2} gqa{g}", "max_err": err,
        "bytes_naive": bytes_naive, "bytes_flash": bytes_flash,
        "traffic_reduction": bytes_naive / bytes_flash,
    }
    log(f"  flash_attention err={err:.4f} "
        f"traffic cut {bytes_naive/bytes_flash:.1f}x")
    return out
