"""Per-phase device time and host-attributed idle of a traced window, read
from the names the program gives its own work.

    python3 bench/phases.py --workload <cell> --seed <n> --seconds <s>

runs one traced window of the cell through ``bench/run.py``'s ``run_cell``
(set-up, window and correctness check unchanged), keeps its trace, and
prints ``run_cell``'s result object with one more key, ``phases``, as the
last line of standard output.  Without a TPU it prints no result and exits
non-zero, as ``bench/run.py`` does.

What it reads (the program's names, copied here so that a rename in the
program shows up as unscoped time instead of moving the yardstick):

* device ops of the ``XLA Ops`` line, each tied to its ``op_name`` by the
  ``tf_op`` stat of its event metadata in the same trace.  An op counts under
  the innermost phase scope (``ringada.trunk``, ``.hot``, ``.head``,
  ``.optimizer``) in its ``op_name``, and under ``ringada.attention``
  wherever that tag appears;
* host spans ``ringada.round`` (one per ``RingSession.step``),
  ``ringada.data``, ``ringada.dispatch`` and ``ringada.sync``, and the
  benchmark's ``bench.window``.

Readings (``phases``), each over the ops that start in the window, mean over
chips, per ``ringada.round`` span in the window:

* ``<phase>_ms_per_round``: device self time of the phase's ops;
* ``attention_ms_per_round``: device self time of the attention core;
* ``unscoped_busy_pct``: share of device self time in ops with no phase
  scope (XLA's copies without metadata among them);
* ``idle_<span>_ms_per_round``: the window's idle gaps intersected with the
  union of that host span's intervals;
* ``clocks``: how many rounds saw their executable start after their
  ``ringada.dispatch`` began and end before their ``ringada.sync`` ended.
"""
from __future__ import annotations

import argparse
import functools
import json
import re
import shutil
import sys
import tempfile
import time
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import numpy as np

if __name__ == "__main__":
    sys.path[:0] = [str(Path(__file__).resolve().parents[1])]

from bench.trace import DEVICE_PLANE, OPS_LINE, WINDOW_SPAN, clip, gaps, \
    length, self_times, subtract, union  # noqa: E402

PHASES = {"ringada.trunk": "trunk", "ringada.hot": "hot",
          "ringada.head": "head", "ringada.optimizer": "optimizer"}
ATTENTION = "ringada.attention"
ROUND_SPAN = "ringada.round"
IDLE_SPANS = {"ringada.data": "data", "ringada.dispatch": "dispatch",
              "ringada.sync": "sync"}
MODULES_LINE = "XLA Modules"
OP_NAME_STAT = "tf_op"
PHASE_RE = re.compile("|".join(re.escape(p) for p in PHASES))


@functools.cache
def _xspace_class():
    """The part of the profiler's ``XSpace`` message this reduction reads,
    declared field by field (the field numbers of ``xplane.proto``)."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    i64, u64, s = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_STRING
    one, many = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    fp = descriptor_pb2.FileDescriptorProto(name="bench_xplane.proto",
                                            package="bench_xplane",
                                            syntax="proto3")

    def message(parent, name, fields):
        m = parent.add(name=name)
        for fname, num, typ, label in fields:
            f = m.field.add(name=fname, number=num, label=label)
            if isinstance(typ, str):           # a message of this file
                f.type, f.type_name = F.TYPE_MESSAGE, f".bench_xplane.{typ}"
            else:
                f.type = typ
        return m

    message(fp.message_type, "XStat", [
        ("metadata_id", 1, i64, one), ("str_value", 5, s, one),
        ("ref_value", 7, u64, one)])
    message(fp.message_type, "XEvent", [
        ("metadata_id", 1, i64, one), ("offset_ps", 2, i64, one),
        ("duration_ps", 3, i64, one)])
    message(fp.message_type, "XLine", [
        ("name", 2, s, one), ("timestamp_ns", 3, i64, one),
        ("events", 4, "XEvent", many)])
    message(fp.message_type, "XEventMetadata", [
        ("id", 1, i64, one), ("name", 2, s, one),
        ("stats", 5, "XStat", many)])
    message(fp.message_type, "XStatMetadata", [
        ("id", 1, i64, one), ("name", 2, s, one)])
    plane = message(fp.message_type, "XPlane", [
        ("name", 2, s, one), ("lines", 3, "XLine", many),
        ("event_metadata", 4, "XPlane.EventMetadataEntry", many),
        ("stat_metadata", 5, "XPlane.StatMetadataEntry", many)])
    for entry, value in (("EventMetadataEntry", "XEventMetadata"),
                         ("StatMetadataEntry", "XStatMetadata")):
        e = message(plane.nested_type, entry, [("key", 1, i64, one),
                                               ("value", 2, value, one)])
        e.options.map_entry = True
    message(fp.message_type, "XSpace", [("planes", 1, "XPlane", many)])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def _events(line) -> List[Tuple[int, float, float]]:
    """(metadata id, start s, end s) on the trace's clock, rounded to whole
    nanoseconds as ``jax.profiler.ProfileData`` rounds them."""
    out = []
    for ev in line.events:
        s = line.timestamp_ns + ev.offset_ps // 1000
        out.append((ev.metadata_id, s * 1e-9,
                    (s + ev.duration_ps // 1000) * 1e-9))
    return out


def _op_names(plane) -> Dict[int, str]:
    """Event metadata id -> the op's ``op_name`` (empty when it has none)."""
    stat_ids = [k for k, v in plane.stat_metadata.items()
                if v.name == OP_NAME_STAT]
    out = {}
    for k, md in plane.event_metadata.items():
        name = ""
        for st in md.stats:
            if st.metadata_id in stat_ids:
                name = (st.str_value if st.str_value else
                        plane.stat_metadata[st.ref_value].name)
        out[k] = name
    return out


def phase_of(op_name: str) -> Optional[str]:
    """The innermost phase scope in an ``op_name``, or None."""
    found = PHASE_RE.findall(op_name)
    return PHASES[found[-1]] if found else None


class PhaseTrace:
    """One trace, reduced to the program's phases and host spans.  Times in
    seconds on the trace's clock."""

    def __init__(self, path: str):
        space = _xspace_class().FromString(Path(path).read_bytes())
        self.window: Optional[Tuple[float, float]] = None
        self.spans: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
        self.chips: Dict[int, Dict[str, object]] = {}
        for plane in space.planes:
            m = DEVICE_PLANE.match(plane.name)
            if m:
                self.chips[int(m.group(1))] = self._device(plane)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for mid, s, e in _events(line):
                        name = plane.event_metadata[mid].name
                        if name == WINDOW_SPAN:
                            self.window = (s, e)
                        elif name == ROUND_SPAN or name in IDLE_SPANS:
                            self.spans[name].append((s, e))
        for v in self.spans.values():
            v.sort()

    @staticmethod
    def _device(plane) -> Dict[str, object]:
        names = _op_names(plane)
        rows, phase, attn, modules = [], [], [], []
        for line in plane.lines:
            if line.name == OPS_LINE:
                for mid, s, e in _events(line):
                    rows.append((s, e))
                    phase.append(phase_of(names[mid]))
                    attn.append(ATTENTION in names[mid])
            elif line.name == MODULES_LINE:
                modules += [(plane.event_metadata[mid].name, s, e)
                            for mid, s, e in _events(line)]
        iv = np.asarray(rows) if rows else np.zeros((0, 2))
        return {"ops": iv, "self": self_times(iv), "phase": phase,
                "attention": np.asarray(attn, bool), "modules": modules}

    # -- reductions over the window ----------------------------------------
    def _lohi(self) -> Tuple[float, float]:
        if self.window is None:
            raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
        return self.window

    def window_s(self) -> float:
        lo, hi = self._lohi()
        return hi - lo

    def _in_window(self, starts: np.ndarray) -> np.ndarray:
        lo, hi = self._lohi()
        return (starts >= lo) & (starts < hi)

    def rounds(self) -> int:
        """``ringada.round`` spans that start in the window."""
        lo, hi = self._lohi()
        return sum(lo <= s < hi for s, _ in self.spans[ROUND_SPAN])

    def self_seconds(self) -> Dict[str, float]:
        """Device self seconds of the ops that start in the window, summed
        over chips: per phase, ``attention``, ``unscoped`` and ``all``."""
        out: Dict[str, float] = defaultdict(float)
        for d in self.chips.values():
            keep = self._in_window(d["ops"][:, 0])
            t = d["self"]
            out["all"] += float(np.sum(t[keep]))
            out["attention"] += float(np.sum(t[keep & d["attention"]]))
            for ph, ti, k in zip(d["phase"], t, keep):
                if k:
                    out[ph or "unscoped"] += float(ti)
        return out

    def idle_seconds(self) -> Dict[str, float]:
        """Idle gaps of the window, per chip summed over chips: all of them
        (``all``) and the part each host span covers."""
        lo, hi = self._lohi()
        out: Dict[str, float] = defaultdict(float)
        for d in self.chips.values():
            idle = gaps(clip(union(d["ops"]), lo, hi), lo, hi)
            out["all"] += length(idle)
            for name, key in IDLE_SPANS.items():
                span = clip(union(np.asarray(self.spans[name]).reshape(-1, 2)),
                            lo, hi)
                out[key] += length(idle) - length(subtract(idle, span))
        return out

    def clocks(self) -> Dict[str, float]:
        """Round k of the window against the k-th run of the window's main
        executable on each chip: did it start after round k's dispatch span
        began, and end before the next sync span after that dispatch ended?
        The least lead (device start less dispatch start) and the least lag
        (sync end less device end) bound the device clock's offset from the
        host's: it lies in [-lag, lead]."""
        lo, hi = self._lohi()
        disp = [iv for iv in self.spans["ringada.dispatch"]
                if lo <= iv[0] < hi]
        syncs = self.spans["ringada.sync"]
        out = {"rounds": len(disp), "module_runs": 0, "pairs": 0,
               "dispatch_first": 0, "synced_after": 0}
        leads, lags = [], []
        for d in self.chips.values():
            runs = [m for m in d["modules"] if lo <= m[1] < hi]
            if not runs:
                continue
            tot: Dict[str, float] = defaultdict(float)
            for name, s, e in runs:
                tot[name] += e - s
            main = max(tot, key=tot.get)
            runs = sorted((s, e) for name, s, e in runs if name == main)
            out["module_runs"] += len(runs)
            for (ds, de), (ms, me) in zip(disp, runs):
                sync = next((iv for iv in syncs if iv[0] >= de), None)
                out["pairs"] += 1
                out["dispatch_first"] += int(ms >= ds)
                leads.append(ms - ds)
                if sync is not None:
                    out["synced_after"] += int(me <= sync[1])
                    lags.append(sync[1] - me)
        if leads:
            out["lead_ms_min"] = 1e3 * min(leads)
        if lags:
            out["lag_ms_min"] = 1e3 * min(lags)
        return out

    def readings(self) -> Optional[Dict[str, object]]:
        """The per-round readings, or None where the trace has no device
        plane or no ``ringada.round`` span in the window."""
        n, chips = self.rounds(), len(self.chips)
        if not n or not chips:
            return None
        busy, idle = self.self_seconds(), self.idle_seconds()
        per = lambda s: 1e3 * s / chips / n
        out: Dict[str, object] = {f"{ph}_ms_per_round": per(busy[ph])
                                  for ph in PHASES.values()}
        out["attention_ms_per_round"] = per(busy["attention"])
        out["unscoped_busy_pct"] = (100.0 * busy["unscoped"] / busy["all"]
                                    if busy["all"] else 0.0)
        for key in IDLE_SPANS.values():
            out[f"idle_{key}_ms_per_round"] = per(idle[key])
        out["idle_ms_per_round"] = per(idle["all"])
        out["busy_ms_per_round"] = per(busy["all"])
        out["rounds"] = n
        out["clocks"] = self.clocks()
        return out


def measure(cell, seed: int, seconds: float, devices, *, limits,
            t_start: float) -> Dict[str, object]:
    """One traced window of ``cell`` through ``run_cell``, with the phase
    readings of its trace under ``phases``."""
    from bench.run import run_cell

    trace_dir = tempfile.mkdtemp(prefix="bench-phases-")
    try:
        out = run_cell(cell, seed, seconds, True, devices, limits=limits,
                       keep_trace=trace_dir, t_start=t_start)
        path = next(Path(trace_dir).rglob("*.xplane.pb"))
        pt = PhaseTrace(str(path))
        ph = pt.readings()
        if ph is not None:
            ph["tokens_per_s_traced"] = (out["attempted"] * cell.tokens_per_call
                                         / pt.window_s())
        out["phases"] = ph
        return out
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    from bench.cell import load_cell
    from bench.correctness import load_limits
    from bench.run import log, use_compile_cache

    cell = load_cell(args.workload)
    limits = load_limits(cell)
    use_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        log(f"phases: {cell.name} needs {cell.chips} TPU chips, JAX found "
            f"{len(devices)} x {devices[0].platform!r}")
        return 2
    out = measure(cell, args.seed, args.seconds, devices[:cell.chips],
                  limits=limits, t_start=t_start)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
