"""Plain reference of a pre-norm dense decoder with one serial adapter per
block, in float32 at HIGHEST matmul precision.  It imports nothing of the
program under test.

Per block, on the residual stream ``h`` (``[rows, seq, D]``):

    h = h + Attn(RMSNorm(h; ln1))        # RoPE on q and k, causal, GQA,
                                         # optional sliding window
    h = h + FFN(RMSNorm(h; ln2))         # GLU (act(x Wg) * x Wu) Wd, or
                                         # act(x Win) Wout
    h = h + gelu_tanh(h Wdown) Wup       # the RingAda serial adapter

then ``logits = RMSNorm(h; final) Whead`` (vocabulary pad columns at -1e30)
and the mean token cross-entropy.  The configuration file's ``sizes`` give
every width; ``layout`` gives the weight tree that the benchmark makes from
the seed and hands to the program, so both read the same leaves.

``control=True`` computes every matrix product from float8 (e4m3) operands,
one scale per row of each operand over its contracted axes, dequantized
into float32: the step below the configuration's bf16.  It must fail the
comparison.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# weights: layout only (the benchmark makes them, see bench/weights.py)
# ---------------------------------------------------------------------------


def padded_vocab(sz: Dict[str, Any]) -> int:
    p = sz["vocab_pad_to"]
    return -(-sz["vocab_size"] // p) * p


def layout(sz: Dict[str, Any]) -> Dict[str, Any]:
    """Leaf -> (shape, dtype, init, std).  Block leaves carry ``[L, 1, ...]``
    (layer, then the one block of the period)."""
    D, H, K, hd = sz["d_model"], sz["n_heads"], sz["n_kv_heads"], sz["head_dim"]
    F, m, L = sz["d_ff"], sz["adapter_bottleneck"], sz["n_layers"]
    V = padded_vocab(sz)
    dt = sz["dtype"]

    def w(*shape, fan_in):
        return ((L, 1) + shape, dt, "normal", 1.0 / math.sqrt(fan_in))

    norm = ((L, 1, D), "float32", "ones", None)
    ffn = ({"w_gate": w(D, F, fan_in=D), "w_up": w(D, F, fan_in=D),
            "w_down": w(F, D, fan_in=F)} if sz["glu"] else
           {"w_in": w(D, F, fan_in=D), "w_out": w(F, D, fan_in=F)})
    block = {
        "ln1": {"scale": norm},
        "attn": {"wq": w(D, H, hd, fan_in=D), "wk": w(D, K, hd, fan_in=D),
                 "wv": w(D, K, hd, fan_in=D),
                 "wo": w(H, hd, D, fan_in=H * hd)},
        "ln2": {"scale": norm},
        "ffn": ffn,
        "adapter": {"w_down": w(D, m, fan_in=D),
                    "w_up": ((L, 1, m, D), dt, "zeros", None)},
    }
    return {
        "embed": {"tok": ((V, D), dt, "normal", 0.02)},
        "final_norm": {"scale": ((D,), "float32", "ones", None)},
        "head": {"w": ((D, V), dt, "normal", 1.0 / math.sqrt(D))},
        "blocks": (block,),
    }


# ---------------------------------------------------------------------------
# the computation
# ---------------------------------------------------------------------------


E4M3_MAX = 448.0


def _fp8(x: jax.Array, axes: Tuple[int, ...]) -> jax.Array:
    """``x`` rounded to float8 e4m3 (scaled so each row's largest element
    lands on the format's largest) and back; the gradient passes straight
    through the rounding, as low-precision training passes it."""
    s = lax.stop_gradient(jnp.max(jnp.abs(x), axis=axes, keepdims=True)
                          / E4M3_MAX)
    s = jnp.where(s > 0, s, 1.0)
    q = (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    return x + lax.stop_gradient(q - x)


def mm(spec: str, a: jax.Array, b: jax.Array, control: bool) -> jax.Array:
    """``einsum(spec, a, b)`` in float32 at HIGHEST; with ``control`` each
    operand is rounded to float8 first, one scale per row over the axes
    that the product contracts."""
    if control:
        ins, out = spec.split("->")
        sa, sb = ins.split(",")
        con = set(sa) & set(sb) - set(out)
        a = _fp8(a, tuple(i for i, c in enumerate(sa) if c in con))
        b = _fp8(b, tuple(i for i, c in enumerate(sb) if c in con))
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=F32)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


ACT = {"silu": jax.nn.silu, "gelu_tanh": gelu_tanh}


def rmsnorm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x [rows, seq, heads, hd]; rotate-half over the whole head."""
    seq, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(seq, dtype=F32)[:, None] * freqs            # [seq, half]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def block(sz, w, h, control):
    """One block; ``w`` holds this layer's leaves in float32."""
    eps = sz["norm_eps"]
    K, H, hd = sz["n_kv_heads"], sz["n_heads"], sz["head_dim"]
    rows, seq, _ = h.shape
    a = w["attn"]
    x = rmsnorm(h, w["ln1"]["scale"], eps)
    q = rope(mm("bsd,dhk->bshk", x, a["wq"], control), sz["rope_theta"])
    k = rope(mm("bsd,dhk->bshk", x, a["wk"], control), sz["rope_theta"])
    v = mm("bsd,dhk->bshk", x, a["wv"], control)
    q = q.reshape(rows, seq, K, H // K, hd)
    s = mm("bqkgh,bskh->bkgqs", q, k, control) / math.sqrt(hd)
    qi, ki = jnp.arange(seq)[:, None], jnp.arange(seq)[None, :]
    keep = ki <= qi
    if sz["sliding_window"] is not None:
        keep &= (qi - ki) < sz["sliding_window"]
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    o = mm("bkgqs,bskh->bqkgh", p, v, control).reshape(rows, seq, H, hd)
    h = h + mm("bshk,hkd->bsd", o, a["wo"], control)

    f = w["ffn"]
    act = ACT[sz["activation"]]
    x = rmsnorm(h, w["ln2"]["scale"], eps)
    if sz["glu"]:
        u = (act(mm("bsd,df->bsf", x, f["w_gate"], control))
             * mm("bsd,df->bsf", x, f["w_up"], control))
        h = h + mm("bsf,fd->bsd", u, f["w_down"], control)
    else:
        u = act(mm("bsd,df->bsf", x, f["w_in"], control))
        h = h + mm("bsf,fd->bsd", u, f["w_out"], control)

    ad = w["adapter"]
    mid = ACT[sz["adapter_activation"]](
        mm("bsd,dm->bsm", h, ad["w_down"], control))
    return h + mm("bsm,md->bsd", mid, ad["w_up"], control)


def _at(blocks, i):
    """Layer ``i`` (traced) of ``[n, 1, ...]`` block leaves, in float32."""
    return jax.tree.map(
        lambda x: lax.dynamic_index_in_dim(x, i, 0, keepdims=False)[0]
        .astype(F32), blocks)


@jax.jit
def embed(tok_table, tokens):
    return jnp.take(tok_table, tokens, axis=0).astype(F32)


@partial(jax.jit, static_argnums=(0, 3, 4, 5))
def trunk(sz_items, blocks, h, lo, hi, control):
    """Frozen layers ``lo..hi-1`` of the local stack, forward only."""
    sz = dict(sz_items)
    return lax.fori_loop(lo, hi, lambda i, hh: block(sz, _at(blocks, i), hh,
                                                     control), h)


def _nll(sz, final_scale, head_w, h, labels, control):
    x = rmsnorm(h, final_scale, sz["norm_eps"])
    logits = mm("bsd,dv->bsv", x, head_w, control)
    V = logits.shape[-1]
    logits = jnp.where(jnp.arange(V) < sz["vocab_size"], logits, -1e30)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return lse - gold                                           # [rows, seq]


@partial(jax.jit, static_argnums=(0, 2, 8))
def hot_grads(sz_items, blocks, lo, trainable, final_scale, h, labels, mask,
              control):
    """(sum of masked nll, its grads w.r.t. ``trainable``) over the hot
    layers ``lo..`` of the local stack, then the head.

    ``trainable``: ``{"adapter": {...} [n_hot, 1, ...], "head": {"w"}}`` in
    float32; the hot layers' other leaves come from ``blocks``.  Each hot
    layer is rematerialized in the backward (memory only; the arithmetic is
    unchanged)."""
    sz = dict(sz_items)
    frozen = {k: v for k, v in blocks.items() if k != "adapter"}

    def loss(tr):
        def body(hh, xs):
            i, ad = xs
            w = {**_at(frozen, i), "adapter": jax.tree.map(lambda x: x[0], ad)}
            return block(sz, w, hh, control), None

        n = jax.tree.leaves(tr["adapter"])[0].shape[0]
        hh, _ = lax.scan(jax.checkpoint(body), h,
                         (lo + jnp.arange(n), tr["adapter"]))
        nll = _nll(sz, final_scale, tr["head"]["w"], hh, labels, control)
        return jnp.sum(nll * mask)

    return jax.value_and_grad(loss)(trainable)
