"""Flash attention (forward) as a Pallas TPU kernel, GQA- and window-aware.

Online softmax over key blocks, tiled for the MXU, in the model's layout:
q [B, H, Sq, hd] and k, v [B, K, Sk, hd], one (bq, hd) query block and one
(bk, hd) key block per grid step, with hd as the full last dimension (80 is
padded to the MXU's 128 by the compiler).  Grouped heads read their K/V
through the k/v ``index_map`` (``h // group``), so repeated K/V never exist
in HBM.

Numerics follow the model's jnp attention: ``q @ k^T`` on the input dtype's
operands (bf16 into the MXU) with f32 accumulation, the scale applied to the
f32 scores, softmax statistics (``m``, ``l``) and the output accumulator in
f32 VMEM scratch that persists across the sequential key-block axis, the
unnormalised probabilities cast to the value dtype for ``p @ v``, and one f32
normalisation at the end.

Key blocks that the causal mask (or the window) removes entirely run no
compute (``pl.when``), and their k/v ``index_map`` is clamped to the nearest
needed block, so the pipeline does not fetch them either.  Only blocks that
straddle the diagonal or the window's edge build a mask.  The causal diagonal
aligns the last query with the last key (``Sk - Sq`` offset).

Block sizes: 512 x 512 by default and in the model (``models/blocks.py``
``FLASH_BLOCK``).  At 16 x 32 heads x 1024 tokens that is 2048 grid steps a
layer, three of every four computed, where 128 x 128 would take 32768 steps
of fixed per-step cost; a 512 x 512 f32 score tile is 1 MiB of VMEM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
NAME = "ringada_flash_fwd"


def _kv_range(iq, *, bq: int, bk: int, n_kb: int, offset: int, causal: bool,
              window):
    """First and last key block that query block ``iq`` attends to."""
    lo, hi = 0, n_kb - 1
    if causal:
        hi = jnp.minimum(hi, (iq * bq + bq - 1 + offset) // bk)
    if window is not None:
        lo = jnp.maximum(lo, (iq * bq + offset - window + 1) // bk)
    return lo, hi


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window, bq: int, bk: int,
            n_kb: int, offset: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    lo, hi = _kv_range(iq, bq=bq, bk=bk, n_kb=n_kb, offset=offset,
                       causal=causal, window=window)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def step(masked: bool):
        q = q_ref[0, 0]                                  # [bq, hd]
        k = k_ref[0, 0]                                  # [bk, hd]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if masked:
            qpos = (iq * bq + offset
                    + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0))
            kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            mask = jnp.ones((bq, bk), bool)
            if causal:
                mask &= kpos <= qpos
            if window is not None:
                mask &= (qpos - kpos) < window
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]                              # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # a row with no key yet keeps m = NEG_INF and sums exp(0); its first
        # real key brings alpha = 0, which wipes that sum
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0]
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    run = (ik >= lo) & (ik <= hi)
    edge = jnp.zeros((), bool)
    if causal:      # the block reaches above the diagonal
        edge |= ik * bk + bk - 1 > iq * bq + offset
    if window is not None:      # the block reaches below the window
        edge |= iq * bq + bq - 1 + offset - ik * bk >= window
    pl.when(run & edge)(lambda: step(True))
    pl.when(run & jnp.logical_not(edge))(lambda: step(False))

    @pl.when(ik == n_kb - 1)
    def _fin():
        o_ref[0, 0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    block_q: int = 512, block_k: int = 512,
                    interpret: bool = False) -> jax.Array:
    """q [B, H, Sq, hd]; k, v [B, K, Sk, hd] with H == K * group (GQA).

    Returns [B, H, Sq, hd] in q's dtype.
    """
    B, H, Sq, hd = q.shape
    K, Sk = k.shape[1], k.shape[2]
    assert H % K == 0, (H, K)
    group = H // K
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    assert Sq <= Sk or not causal, "every query needs a key"
    n_qb, n_kb = Sq // bq, Sk // bk
    kv_range = functools.partial(_kv_range, bq=bq, bk=bk, n_kb=n_kb,
                                 offset=Sk - Sq, causal=causal, window=window)

    def kv_index(b, h, iq, ik):
        lo, hi = kv_range(iq)
        return b, h // group, jnp.clip(ik, lo, hi), 0

    return pl.pallas_call(
        functools.partial(_kernel, scale=1.0 / math.sqrt(hd), causal=causal,
                          window=window, bq=bq, bk=bk, n_kb=n_kb,
                          offset=Sk - Sq),
        grid=(B, H, n_qb, n_kb),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, hd), kv_index),
            pl.BlockSpec((1, 1, bk, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        name=NAME,
        interpret=interpret,
    )(q, k, v)
