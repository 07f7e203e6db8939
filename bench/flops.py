"""Operations a training call requires, from shapes alone.

For a pre-norm dense decoder with adapters (the configurations' ``sizes``),
per token, counting a multiply-add as two operations:

  * forward through every block and the head;
  * activation gradients through every hot block (at or above the
    span-aligned boundary) and the head;
  * weight gradients of the trainable leaves only: the hot adapters and the
    head;
  * causal attention counted as half of the score and value products (the
    mean query sees half the keys; fewer under a sliding window);
    recomputation is not counted.

These are the operations the algorithm needs.  What the program executes on
top (masked stages, rematerialization, padded tiles) is waste that the
utilization exposes.
"""
from __future__ import annotations

from typing import Any, Dict


def keys_per_query(sz: Dict[str, Any], seq: int, dense: bool) -> float:
    """Keys a query attends to, on average: under the causal (and window)
    mask when required; all ``seq`` where a dense score matrix is
    computed."""
    if dense:
        return float(seq)
    w = sz.get("sliding_window") or seq
    return sum(min(q + 1, w) for q in range(seq)) / seq


def block_forward(sz: Dict[str, Any], seq: int, *, dense: bool = False
                  ) -> float:
    """One block's forward operations per token (``dense``: scores over
    every key, as a masked dense score matrix computes them)."""
    D, H, K, hd = sz["d_model"], sz["n_heads"], sz["n_kv_heads"], sz["head_dim"]
    F, m = sz["d_ff"], sz["adapter_bottleneck"]
    keys = keys_per_query(sz, seq, dense)
    proj = 2 * D * (H * hd) * 2 + 2 * D * (K * hd) * 2      # q, o; k, v
    core = 2 * 2 * keys * H * hd                            # scores, values
    ffn = 2 * D * F * (3 if sz["glu"] else 2)
    adapter = 2 * 2 * D * m
    return float(proj + core + ffn + adapter)


def head_forward(sz: Dict[str, Any], vocab: int = None) -> float:
    return 2.0 * sz["d_model"] * (vocab or sz["vocab_size"])


def forward_per_token(sz: Dict[str, Any], seq: int, *, dense: bool = False,
                      vocab: int = None) -> float:
    return (sz["n_layers"] * block_forward(sz, seq, dense=dense)
            + head_forward(sz, vocab))


def train_per_token(sz: Dict[str, Any], seq: int, boundary: int) -> float:
    """Required operations per training token at ``boundary`` frozen
    layers."""
    D, H, hd, m = sz["d_model"], sz["n_heads"], sz["head_dim"], \
        sz["adapter_bottleneck"]
    hot = sz["n_layers"] - boundary
    fwd = forward_per_token(sz, seq)
    # input gradients cost what the forward products cost; the attention
    # core's backward is twice its forward (dV, dP, then dQ and dK)
    core = 2 * 2 * keys_per_query(sz, seq, False) * H * hd
    act = hot * (block_forward(sz, seq) + core) + head_forward(sz)
    wgrad = hot * 2 * 2 * D * m + head_forward(sz)
    return fwd + act + wgrad


def per_call(cell, boundary: int) -> float:
    """Required operations of one ``RingSession.step`` of ``cell``."""
    return cell.tokens_per_call * train_per_token(
        cell.sizes, cell.traffic["seq_len"], boundary)
