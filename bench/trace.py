"""Reduction of a JAX profiler trace (`.xplane.pb`) to the benchmark's
device numbers: busy time, idle gaps, per-operation and per-kernel time,
and the host spans each idle gap falls in.

Device planes are those named `/device:TPU:<n>`. On each, the line
"XLA Ops" holds one event per operation that ran; "XLA Modules" one per
compiled program. Host planes hold the benchmark's own spans
(`bench.run`, `bench.fit_step`, ...), written with
`jax.profiler.TraceAnnotation`, on the same clock.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


# "%gru_cell_pallas.42 = f32[128,512]{...} custom-call(...)": the
# instruction's name, its numeric suffixes, and its opcode
_HLO = re.compile(r"%([\w\-]+?)((?:\.\d+)*)(?:\.clone)* = .*? ([\w\-]+)\(")
# operations that only hold other operations: their time is their body's
WRAPPERS = ("while", "conditional", "call")


def parse_op(text: str) -> tuple:
    """(base name, instruction name, opcode) of one "XLA Ops" event."""
    m = _HLO.match(text)
    if m is None:
        name = text.split(" ", 1)[0].lstrip("%")
        return name, name, ""
    return m.group(1), m.group(1) + m.group(2), m.group(3)


@dataclass
class Trace:
    """Device events as arrays (ns), per chip, plus the host spans. Event
    names are ids into `names`, whose entries are `parse_op` triples."""
    ops: list = field(default_factory=list)       # per chip: (ids, start, dur)
    modules: list = field(default_factory=list)   # per chip: (names, start, dur)
    spans: list = field(default_factory=list)     # (name, start, end)
    window: tuple = (0, 0)                        # traced window (ns)
    names: list = field(default_factory=list)     # id -> parse_op triple

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _line_arrays(line, table=None):
    """(names, start, dur) of a line. With `table` (text -> id), names are
    interned ids: a traced scan repeats a few hundred texts millions of
    times."""
    names, start, dur = [], [], []
    for ev in line.events:
        text = ev.name
        names.append(text if table is None else table.setdefault(
            text, len(table)))
        start.append(ev.start_ns)
        dur.append(ev.duration_ns)
    return (np.array(names, object if table is None else np.int64),
            np.array(start, np.float64), np.array(dur, np.float64))


def load(path: str, window=None) -> Trace:
    """Read a trace. `window` = (start_ns, end_ns) of the measured window on
    the trace's clock; by default, the extent of the `bench.` host spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    t = Trace()
    table = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE in lines:
                t.ops.append(_line_arrays(lines[OPS_LINE], table))
                t.modules.append(_line_arrays(lines[MODULES_LINE])
                                 if MODULES_LINE in lines else
                                 (np.array([], object), np.zeros(0),
                                  np.zeros(0)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        t.spans.append((ev.name, ev.start_ns,
                                        ev.start_ns + ev.duration_ns))
    t.spans.sort(key=lambda s: s[1])
    t.names = [None] * len(table)
    for text, i in table.items():
        t.names[i] = parse_op(text)
    if window is None:
        window = ((min(s[1] for s in t.spans), max(s[2] for s in t.spans))
                  if t.spans else _ops_extent(t))
    t.window = (float(window[0]), float(window[1]))
    return t


def _ops_extent(t: Trace):
    starts = [s.min() for _, s, _ in t.ops if s.size]
    ends = [(s + d).max() for _, s, d in t.ops if s.size]
    return (min(starts), max(ends)) if starts else (0.0, 0.0)


def _clip(start, dur, window):
    lo = np.maximum(start, window[0])
    hi = np.minimum(start + dur, window[1])
    keep = hi > lo
    return lo[keep], hi[keep], keep


def busy_intervals(start, dur, window):
    """Union of [start, start+dur) clipped to the window, as sorted,
    disjoint (lo, hi) arrays."""
    lo, hi, _ = _clip(start, dur, window)
    if lo.size == 0:
        return lo, hi
    order = np.argsort(lo, kind="stable")
    lo, hi = lo[order], hi[order]
    run_hi = np.maximum.accumulate(hi)
    new = np.ones(lo.size, bool)
    new[1:] = lo[1:] > run_hi[:-1]
    idx = np.flatnonzero(new)
    ends = np.append(idx[1:], lo.size) - 1
    return lo[idx], run_hi[ends]


def busy_s(t: Trace) -> float:
    """Seconds in which some operation ran, averaged over the chips."""
    if not t.ops:
        return 0.0
    per_chip = []
    for _, start, dur in t.ops:
        lo, hi = busy_intervals(start, dur, t.window)
        per_chip.append(float((hi - lo).sum()) * 1e-9)
    return float(np.mean(per_chip))


def _select(t: Trace, ids, match):
    hit = np.array([bool(match(*n)) for n in t.names] or [False], bool)
    return hit[ids] if ids.size else np.zeros(0, bool)


def op_time_s(t: Trace, match) -> tuple:
    """(seconds, count) of the device operations for which
    `match(base, name, opcode)` holds, summed over chips and clipped to
    the window. Kernels are matched by base name: a Pallas kernel runs as
    a custom call named after its `pallas_call` function."""
    total, count = 0.0, 0
    for ids, start, dur in t.ops:
        sel = _select(t, ids, match)
        if not sel.any():
            continue
        lo, hi, keep = _clip(start[sel], dur[sel], t.window)
        total += float((hi - lo).sum()) * 1e-9
        count += int(keep.sum())
    return total, count


def op_time_by_name(t: Trace, match) -> dict:
    """{instruction name: (seconds, count)} of the device operations for
    which `match(base, name, opcode)` holds, as `op_time_s` sums them."""
    out = {}
    for name in sorted({n[1] for n in t.names if match(*n)}):
        secs, count = op_time_s(t, lambda base, nm, op: nm == name
                                and match(base, nm, op))
        if count:
            out[name] = (secs, count)
    return out


def module_busy_s(t: Trace, match) -> float:
    """Seconds of the window covered by programs whose name satisfies
    `match`, averaged over chips."""
    per_chip = []
    for names, start, dur in t.modules:
        sel = np.array([bool(match(n)) for n in names], bool)
        lo, hi = busy_intervals(start[sel], dur[sel], t.window)
        per_chip.append(float((hi - lo).sum()) * 1e-9)
    return float(np.mean(per_chip)) if per_chip else 0.0


def top_ops(t: Trace, k: int = 10) -> list:
    """[[name, seconds], ...] of the k instructions with the most device
    time in the window, summed over chips; loops and calls, which only
    hold other operations, are left out."""
    acc = defaultdict(float)
    for ids, start, dur in t.ops:
        lo, hi, keep = _clip(start, dur, t.window)
        secs = np.bincount(ids[keep], (hi - lo) * 1e-9,
                           minlength=len(t.names))
        for i in np.flatnonzero(secs):
            base, name, opcode = t.names[i]
            if opcode not in WRAPPERS:
                acc[name] += float(secs[i])
    return [[n, s] for n, s in sorted(acc.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(t: Trace, k: int = 10) -> list:
    """[[label, seconds], ...] of the k longest stretches of the window in
    which the first chip ran nothing, each labelled by the innermost
    `bench.` host span open at the gap's middle ("no span" outside any)."""
    if not t.ops:
        return []
    _, start, dur = t.ops[0]
    lo, hi = busy_intervals(start, dur, t.window)
    g_lo = np.concatenate([[t.window[0]], hi])
    g_hi = np.concatenate([lo, [t.window[1]]])
    gap = g_hi - g_lo
    out = []
    for i in np.argsort(-gap)[:k]:
        if gap[i] <= 0:
            break
        mid = 0.5 * (g_lo[i] + g_hi[i])
        label = "no span"
        for name, s0, s1 in t.spans:
            if s0 <= mid < s1:
                label = name          # spans sorted by start: innermost last
        out.append([label, float(gap[i]) * 1e-9])
    return out
