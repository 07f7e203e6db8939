"""Whether the timed path trains correctly: the program's first three calls
of ``RingSession.step``, made in set-up through the window's own call and
feed, against the plain reference of the configuration.

Readings, on both sides, of the *trainable leaf-rows* (the head, and each
hot layer's adapter ``w_down`` and ``w_up``):

  * ``losses``: every optimizer step's loss (a ring call makes one step per
    client, in owner order);
  * ``first``: each leaf-row's first gradient as the optimizer got it,
    ``||m|| / (1 - beta1)`` from the first moment after the first call;
  * ``delta``: ``||theta_3 - theta_0||`` per leaf-row after three calls;
  * ``frozen_changed`` (program only): rows of frozen leaves whose bits
    changed, by exact fingerprints (``bench/weights.py``).

Numbers compared (``compare``), each against the cell's limit in
``bench/limits/<cell>.json``:

  * ``loss_gap``: the largest ``|loss - loss_ref|`` over the steps;
  * ``grad_gap`` / ``delta_gap``: over leaf-rows, the largest gap between the
    program's norm and the reference's, over the reference's norm of that
    leaf-row or the median leaf-row's, whichever is larger.  Leaf-rows whose
    reference gradient is under a thousandth of the median leaf-row's are
    left out (of ``grad_gap`` by their first gradient, of ``delta_gap`` by
    their largest over the three calls): they move by round-off alone;
  * ``frozen_changed``: exact, limit 0.
"""
from __future__ import annotations

import importlib
import json
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.cell import BENCH_DIR, Cell
from bench.weights import fingerprints, make_weights

CALLS = 3
TINY = 1e-3                      # of the median leaf-row: round-off only
NUMBERS = ("loss_gap", "grad_gap", "delta_gap", "frozen_changed")


def reference_module(cell: Cell):
    return importlib.import_module(
        f"bench.references.{cell.config['reference']}")


def sz_items(cell: Cell):
    return tuple(sorted(cell.sizes.items()))


def boundary_of(cell: Cell) -> int:
    """Frozen layers: ``L - depth``; a ring aligns it down to a span edge."""
    t, L = cell.traffic, cell.sizes["n_layers"]
    raw = max(L - t["depth"], 0)
    if t["backend"] == "pjit":
        return raw
    span = L // t["n_stages"]
    return raw // span * span


def placement(cell: Cell, devices):
    """Leaf path -> sharding for the weights handed to the program: one
    device, or the ring's mesh with every block leaf split by layer over
    the stages (what the program's stage stack holds) and the rest
    replicated."""
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)

    S = cell.traffic["n_stages"]
    if cell.traffic["backend"] == "pjit" or S == 1:
        one = SingleDeviceSharding(devices[0])
        return lambda path: one
    mesh = Mesh(np.array(devices[:S]), ("stage",))
    split, rep = NamedSharding(mesh, P("stage")), NamedSharding(mesh, P())
    return lambda path: split if path[0].key == "blocks" else rep


def _f32(x) -> np.ndarray:
    return np.asarray(x).astype(np.float32)


def _norm(x: np.ndarray) -> float:
    return float(np.sqrt(np.sum(np.square(x, dtype=np.float64))))


@jax.jit
def _dev_norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


@jax.jit
def _dev_diff_norm(x, y):
    return _dev_norm(x.astype(jnp.float32) - y.astype(jnp.float32))


def _local(tree, device):
    """This device's shard of every leaf (no copy)."""
    return jax.tree.map(
        lambda x: next(s.data for s in x.addressable_shards
                       if s.device == device), tree)


class ProgramProbe:
    """Reads the program's state around its first three calls.  Adapter
    rows are read on the host (they are small); the head is measured on the
    device, against a copy of its starting value kept there until the third
    call."""

    def __init__(self, cell: Cell, weights: Dict[str, Any], device):
        self.cell, self.device = cell, device
        self.L = cell.sizes["n_layers"]
        self.b = boundary_of(cell)
        self.beta1 = cell.traffic["optimizer"]["beta1"]
        self.losses: List[float] = []
        self.ad0 = self._adapters(weights["blocks"][0]["adapter"], lead=1)
        self.head0 = jnp.copy(_local(weights["head"]["w"], device))
        self.fp0 = fingerprints(*self._frozen(weights["blocks"][0],
                                              weights, lead=1))
        self.first: Dict[str, float] = {}
        self.delta: Dict[str, float] = {}
        self.frozen_changed: Optional[int] = None

    # -- views of the program's state -------------------------------------
    def _state(self, sess):
        """(block leaves, shared leaves, adapter m, head m, lead axes)."""
        be = sess.backend
        if be.kind == "pjit":
            p, m = be._params, be._opt["m"]
            return p["blocks"][0], p, m["adapters"][0], m["head"], 1
        d = be.driver
        m = d.opt_state["m"]
        return d.stage_blocks, d.shared, m["adapter"], m["head"], 2

    def _adapters(self, adapter, lead: int) -> Dict[str, np.ndarray]:
        """Hot adapter rows on the host, from ``lead`` layer axes."""
        out = {}
        for k, x in adapter.items():
            a = _f32(x)
            rows = a.reshape((self.L,) + a.shape[lead:])
            for layer in range(self.b, self.L):
                out[f"adapter.{k}.{layer}"] = rows[layer]
        return out

    def _frozen(self, blocks, shared, lead: int):
        leaves, leads = {}, {}
        for path, x in jax.tree_util.tree_flatten_with_path(blocks)[0]:
            name = "blocks." + ".".join(p.key for p in path)
            leaves[name], leads[name] = x, lead
        for k in ("embed", "final_norm"):
            for path, x in jax.tree_util.tree_flatten_with_path(shared[k])[0]:
                name = k + "." + ".".join(p.key for p in path)
                leaves[name], leads[name] = x, 0
        return leaves, leads

    # -- the three calls ---------------------------------------------------
    def record(self, metrics) -> None:
        """Losses of one materialized call, in owner order."""
        if "losses" in metrics.extras:
            self.losses += [float(x) for x in metrics.extras["losses"]]
        else:
            self.losses.append(float(metrics.loss))

    def after_first(self, sess) -> None:
        _, _, m_ad, m_head, lead = self._state(sess)
        first = {k: _norm(x) for k, x in self._adapters(m_ad, lead).items()}
        first["head.w"] = float(_dev_norm(m_head["w"]))
        self.first = {k: x / (1.0 - self.beta1) for k, x in first.items()}

    def after_third(self, sess) -> None:
        blocks, shared, _, _, lead = self._state(sess)
        ad3 = self._adapters(blocks["adapter"], lead)
        self.delta = {k: _norm(ad3[k] - self.ad0[k]) for k in ad3}
        self.delta["head.w"] = float(_dev_diff_norm(
            _local(shared["head"]["w"], self.device), self.head0))
        self.head0 = None
        fp = fingerprints(*self._frozen(blocks, shared, lead))
        changed = 0
        for name, before in self.fp0.items():
            diff = before != fp[name]
            if name.startswith("blocks.adapter."):
                diff = diff[:self.b]          # hot rows are meant to move
            changed += int(np.sum(diff))
        self.frozen_changed = changed

    def readings(self) -> Dict[str, Any]:
        return {"losses": self.losses, "first": self.first,
                "delta": self.delta, "frozen_changed": self.frozen_changed}


# ---------------------------------------------------------------------------
# the reference: the same three calls, from the seed
# ---------------------------------------------------------------------------


def _adam(form: str, opt_items: tuple, g, m, v, p, count: int):
    """One AdamW step on float32 arrays -> (m, v, p rounded to the weights'
    dtype and back).  ``ring``: raw moments, constant lr (the ring's
    per-client update); ``pjit``: bias-corrected, linear warmup."""
    opt = dict(opt_items)
    b1, b2, eps = opt["beta1"], opt["beta2"], opt["eps"]
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    lr = opt["learning_rate"]
    if form == "pjit":
        lr = lr * min(1.0, (count + 1) / max(opt["warmup_steps"], 1))
        mh, vh = m / (1 - b1 ** count), v / (1 - b2 ** count)
    else:
        mh, vh = m, v
    upd = mh / (jnp.sqrt(vh) + eps) + opt["weight_decay"] * p
    return m, v, (p - lr * upd)


_adam_jit = jax.jit(_adam, static_argnums=(0, 1, 6))


@jax.jit
def _norms(tree):
    """Per leaf-row norms of a reference trainable tree (head: one row;
    adapters: one per hot layer)."""
    sq = lambda x, axes: jnp.sqrt(jnp.sum(jnp.square(x), axis=axes))
    return {"head": sq(tree["head"]["w"], None),
            "adapter": {k: sq(x, tuple(range(1, x.ndim)))
                        for k, x in tree["adapter"].items()}}


def _named(norms, b: int) -> Dict[str, float]:
    """``_norms`` output -> {leaf-row name: norm}, named as the probe's."""
    out = {"head.w": float(norms["head"])}
    for k, x in norms["adapter"].items():
        for i, val in enumerate(np.asarray(x)):
            out[f"adapter.{k}.{b + i}"] = float(val)
    return out


def _owner_batches(cell: Cell, batches) -> List[tuple]:
    """The optimizer steps of the recorded calls: (tokens, labels) with
    ``[rows, seq]``, in the order the program takes them."""
    steps = []
    for b in batches:
        if isinstance(b, dict):
            steps.append((b["tokens"], b["labels"]))
        else:
            _, tok, lab = b
            seq = tok.shape[-1]
            for u in range(tok.shape[0]):
                steps.append((tok[u].reshape(-1, seq), lab[u].reshape(-1, seq)))
    return steps


def _mask(rows: int, seq: int, fault: Optional[str]) -> np.ndarray:
    mask = np.ones((rows, seq), np.float32)
    if fault == "half_batch":                     # the rest's mean
        if rows >= 2:
            mask[rows // 2:] = 0
        else:
            mask[:, seq // 2:] = 0
    return mask


def reference_readings(cell: Cell, seed: int, batches, devices, *,
                       control: bool = False,
                       fault: Optional[str] = None) -> Dict[str, Any]:
    """Readings of the reference over the recorded batches, on weights made
    again from ``seed``.  ``control`` runs it in float8; ``fault`` plants one
    of the faults that the comparison must catch: ``unchanged`` (every step
    returns its state unchanged), ``half_batch`` (the loss over half of each
    batch) or ``no_exchange`` (the frozen trunk's output never reaches the
    hot layers, which get the embeddings)."""
    ref = reference_module(cell)
    sz, t = cell.sizes, cell.traffic
    items = sz_items(cell)
    L, b = sz["n_layers"], boundary_of(cell)
    S = 1 if t["backend"] == "pjit" else t["n_stages"]
    span = L // S
    form = "pjit" if t["backend"] == "pjit" else "ring"
    opt = t["optimizer"]
    if b < (S - 1) * span:
        raise NotImplementedError("the reference keeps the hot layers on one "
                                  "chip: hot layers must lie in the last stage")
    weights = make_weights(ref.layout(sz), seed, placement(cell, devices))
    devs = devices[:S]
    hot_dev = devs[-1]
    blocks = [_local(weights["blocks"][0], d) for d in devs]
    tok_table = _local(weights["embed"]["tok"], devs[0])
    final_scale = _local(weights["final_norm"]["scale"], hot_dev)
    lo_hot = b - (S - 1) * span
    hot_ad = jax.tree.map(lambda x: x[lo_hot:], blocks[-1]["adapter"])
    tr = {"adapter": jax.tree.map(lambda x: x.astype(jnp.float32), hot_ad),
          "head": {"w": _local(weights["head"]["w"], hot_dev)
                   .astype(jnp.float32)}}
    dtype = jnp.dtype(sz["dtype"])
    tr0 = jax.tree.map(jnp.copy, tr)
    opt_items = tuple(sorted(opt.items()))
    m = jax.tree.map(jnp.zeros_like, tr)
    v = jax.tree.map(jnp.zeros_like, tr)

    # the frozen trunk does not train: all steps' inputs to the hot layers
    steps = _owner_batches(cell, batches)
    per_call = len(steps) // len(batches)
    h_hot = []
    for tok, lab in steps:
        h = ref.embed(tok_table, jnp.asarray(tok))
        if fault != "no_exchange":
            for s, d in enumerate(devs):
                hi = min(span, b - s * span)
                if hi <= 0:
                    break
                h = ref.trunk(items, blocks[s], jax.device_put(h, d), 0, hi,
                              control)
        h_hot.append(jax.device_put(h, hot_dev))

    losses, first, gmax = [], {}, {}
    for k, ((tok, lab), h) in enumerate(zip(steps, h_hot)):
        rows, seq = tok.shape
        rows_per_block = max(1, min(rows, 8192 // seq))
        mask = _mask(rows, seq, fault)
        total, grads = 0.0, None
        for r0 in range(0, rows, rows_per_block):
            sl = slice(r0, r0 + rows_per_block)
            val, g = ref.hot_grads(items, blocks[-1], lo_hot, tr, final_scale,
                                   h[sl], jax.device_put(lab[sl], hot_dev),
                                   jax.device_put(mask[sl], hot_dev), control)
            total = total + val
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        n = float(mask.sum())
        losses.append(float(total) / n)
        grads = jax.tree.map(lambda x: x / n, grads)
        for name, x in _named(_norms(grads), b).items():
            gmax[name] = max(gmax.get(name, 0.0), x)
        if fault != "unchanged":
            new = jax.tree.map(
                lambda g_, m_, v_, p_: _adam_jit(form, opt_items, g_, m_, v_,
                                                 p_, k + 1),
                grads, m, v, tr)
            m = jax.tree.map(lambda t3: t3[0], new, is_leaf=_is_triple)
            v = jax.tree.map(lambda t3: t3[1], new, is_leaf=_is_triple)
            tr = jax.tree.map(
                lambda t3: t3[2].astype(dtype).astype(jnp.float32), new,
                is_leaf=_is_triple)
        if k + 1 == per_call:
            first = {name: x / (1 - opt["beta1"])
                     for name, x in _named(_norms(m), b).items()}
    delta = _named(_norms(jax.tree.map(jnp.subtract, tr, tr0)), b)
    return {"losses": losses, "first": first, "delta": delta, "gmax": gmax}


def _is_triple(x):
    return isinstance(x, tuple) and len(x) == 3


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------


def load_limits(cell: Cell) -> Dict[str, Optional[float]]:
    with open(BENCH_DIR / "limits" / f"{cell.name}.json") as f:
        return json.load(f)["limits"]


def _rel_gap(prog: Dict[str, float], ref: Dict[str, float],
             gate: Dict[str, float]) -> float:
    """Largest |prog - ref| / max(ref, median ref) over the leaf-rows whose
    ``gate`` reading is at least TINY of the median gate reading."""
    med_gate = float(np.median(list(gate.values())))
    keep = [k for k in ref if gate[k] >= TINY * med_gate]
    if not keep or any(k not in prog for k in keep):
        return float("nan")
    med = float(np.median([ref[k] for k in keep]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep)


def numbers(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    lp, lr = prog["losses"], ref["losses"]
    loss_gap = (max(abs(a - b) for a, b in zip(lp, lr))
                if len(lp) == len(lr) and lp else float("nan"))
    return {
        "loss_gap": loss_gap,
        "grad_gap": _rel_gap(prog["first"], ref["first"], ref["first"]),
        "delta_gap": _rel_gap(prog["delta"], ref["delta"], ref["gmax"]),
        "frozen_changed": (float("nan") if prog["frozen_changed"] is None
                           else float(prog["frozen_changed"])),
    }


def verdict(nums: Dict[str, float], limits: Dict[str, Optional[float]]):
    """(correct, {name: {"value", "limit"}}): a number without a limit is
    printed and not compared; one that is not a number fails."""
    shown, ok = {}, True
    for name in NUMBERS:
        lim = limits.get(name)
        val = nums[name]
        shown[name] = {"value": val, "limit": lim}
        if lim is not None and not val <= lim:
            ok = False
    return ok, shown
