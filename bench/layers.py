"""m4's event-step layers and host spans, read from the profiler trace.

The program names each layer of its event step with a `jax.named_scope`
(`m4.departure`, `m4.snapshot`, `m4.temporal`, `m4.spatial`, `m4.heads`,
`m4.scatter`), and its `repro.obs` spans (`m4.run` > `m4.build`,
`m4.scan`, `m4.result`) reach the profiler's trace while it collects.

The trace that `bench.trace.load` hands the readers keeps neither: its
device ops carry instruction names only, and its host spans are the
benchmark's own. So this module reads the `.xplane.pb` itself, in the
protobuf wire format (`XSpace` > `XPlane`): the JAX name stack of each
device op is the `tf_op` stat of its event metadata, keyed by the
program id and the instruction's name; the host planes' few events are
decoded for the `bench.` and `m4.` spans. The device planes' op events,
millions in a traced call, are skipped by their length.

A scope's device time is the union of its ops' intervals inside the
window. The TPU tracer can leave out a share of a long call's events, so
the time is taken per recorded iteration of the scan body: the median,
over the scoped instructions, of the times each was recorded.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import tempfile

import numpy as np

from bench import trace

SCOPE_PREFIX = "m4."
HOST_PREFIXES = (trace.SPAN_PREFIX, SCOPE_PREFIX)
_PROGRAM_ID = re.compile(r"\((\d+)\)$")


# ------------------------------------------------------------ wire format
def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo, hi):
    """(field number, value) of the message in buf[lo:hi]: an int for a
    varint, (start, end) for a length-delimited field; fixed-width fields
    (doubles) are skipped."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield num, v
        elif wire == 2:
            n, i = _varint(buf, i)
            yield num, (i, i + n)
            i += n
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"xplane: wire type {wire} at byte {i}")


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_value(buf, span):
    """The value (field 2) of one map entry, as (start, end)."""
    for num, v in _fields(buf, *span):
        if num == 2:
            return v
    return (span[1], span[1])


def _stats(buf, span):
    """{stat metadata id: value} of an event metadata's `XStat`s (field
    5): ints for numbers, ("ref", id) for a reference, strings for text."""
    out = {}
    for num, v in _fields(buf, *span):
        if num != 5:
            continue
        mid, val = None, None
        for n, x in _fields(buf, *v):
            if n == 1:
                mid = x
            elif n in (3, 4):           # uint64, int64
                val = x
            elif n == 5:                # str
                val = _text(buf, x)
            elif n == 7:                # ref to a stat metadata's name
                val = ("ref", x)
        out[mid] = val
    return out


@dataclasses.dataclass
class Xplane:
    """What this module reads of an `.xplane.pb`."""
    # (program id, instruction name) -> tf_op name stack
    stacks: dict = dataclasses.field(default_factory=dict)
    # (name, start_ns, end_ns) of the `bench.` and `m4.` host events
    spans: list = dataclasses.field(default_factory=list)

    @property
    def window(self):
        """Extent of the `bench.` spans, as `bench.trace.load` sets it."""
        b = [s for s in self.spans if s[0].startswith(trace.SPAN_PREFIX)]
        if not b:
            return None
        return (min(s[1] for s in b), max(s[2] for s in b))


def _plane(buf, span, out):
    name, lines, events, stat_names = "", [], [], {}
    for num, v in _fields(buf, *span):
        if num == 2:
            name = _text(buf, v)
        elif num == 3:
            lines.append(v)
        elif num == 4:
            events.append(_map_value(buf, v))
        elif num == 5:
            sm = dict(_fields(buf, *_map_value(buf, v)))
            if 1 in sm and 2 in sm:
                stat_names[sm[1]] = _text(buf, sm[2])
    if name.startswith("/device:TPU:"):
        ids = {n: i for i, n in stat_names.items()}
        tf_op, program = ids.get("tf_op"), ids.get("program_id")
        for span_md in events:
            md = dict(_fields(buf, *span_md))
            text = _text(buf, md[2]) if 2 in md else ""
            stats = _stats(buf, span_md)
            stack = stats.get(tf_op)
            if isinstance(stack, tuple):
                stack = stat_names.get(stack[1])
            if stack and program in stats:
                out.stacks[(stats[program], trace.parse_op(text)[1])] = stack
    elif name.startswith("/host:"):
        wanted = {}
        for span_md in events:
            md = dict(_fields(buf, *span_md))
            text = _text(buf, md[2]) if 2 in md else ""
            if 1 in md and text.startswith(HOST_PREFIXES):
                wanted[md[1]] = text
        for line in lines:
            ts, evs = 0, []
            for num, v in _fields(buf, *line):
                if num == 3:
                    ts = v
                elif num == 4:
                    evs.append(v)
            for lo, hi in evs:
                key, i = _varint(buf, lo)       # metadata_id comes first
                if key != 8 or _varint(buf, i)[0] not in wanted:
                    continue
                e = dict(_fields(buf, lo, hi))
                start = ts + e.get(2, 0) / 1e3
                out.spans.append((wanted[e[1]], start,
                                  start + e.get(3, 0) / 1e3))


def read_xplane(path: str) -> Xplane:
    """Name stacks of the device ops and the `bench.`/`m4.` host spans."""
    with open(path, "rb") as fh:
        buf = fh.read()
    out = Xplane()
    for num, v in _fields(buf, 0, len(buf)):
        if num == 1 and isinstance(v, tuple):
            _plane(buf, v, out)
    out.spans.sort(key=lambda s: s[1])
    return out


# ------------------------------------------------------- the traced call
def _find_xplane(t: trace.Trace):
    """The reading of the file `t` was loaded from. The harness traces
    into a fresh `bench-trace-*` directory of the temporary directory and
    hands the readers the loaded trace alone; the file is the one there
    whose `bench.` spans give `t.window`."""
    paths = glob.glob(os.path.join(tempfile.gettempdir(), "bench-trace-*",
                                   "**", "*.xplane.pb"), recursive=True)
    for path in sorted(paths, key=os.path.getmtime, reverse=True):
        x = read_xplane(path)
        w = x.window
        if w is not None and np.allclose(w, t.window, rtol=0, atol=1.0):
            return x
    return None


def _reading(ctx):
    """(`_find_xplane`, `_layer_table`) of the context's trace, kept in
    the context: the harness hands every reader of a run the same one."""
    t = ctx["trace"]
    kept = ctx.get("layers.reading")
    if kept is None or kept[0] is not t:
        x = _find_xplane(t)
        table = _layer_table(t, x) if x is not None else ({}, 0.0)
        kept = ctx["layers.reading"] = (t, x, table)
    return kept[1:]


def _scopes(stack) -> frozenset:
    """The `m4.` scopes on a name stack ("jit(f)/while/body/m4.heads/dot")."""
    if not stack:
        return frozenset()
    return frozenset(c for c in re.split(r"[/:]", stack)
                     if c.startswith(SCOPE_PREFIX))


def _layer_table(t: trace.Trace, x: Xplane):
    """({scope: seconds}, recorded steps) of the window, over the chips.
    Each op is keyed by its instruction and the program that ran it, the
    one whose module event holds the op's start."""
    secs, counts = {}, {}
    w0, w1 = t.window
    for (ids, start, dur), (mnames, mstart, mdur) in zip(t.ops, t.modules):
        order = np.argsort(mstart, kind="stable")
        mstart, mend = mstart[order], (mstart + mdur)[order]
        progs = [_PROGRAM_ID.search(str(n)) for n in mnames[order]]
        progs = [int(m.group(1)) if m else None for m in progs]
        mod = np.searchsorted(mstart, start, side="right") - 1
        inside = (mod >= 0) & (start < mend[np.maximum(mod, 0)])
        n = len(t.names)
        codes, inv = np.unique(np.where(inside, mod, -1) * n + ids + n,
                               return_inverse=True)
        inv = inv.reshape(-1)
        keys = [(progs[c // n - 1] if c >= n else None, t.names[c % n][1])
                for c in codes.tolist()]
        scopes = [_scopes(x.stacks.get(k)) for k in keys]
        recorded = np.bincount(
            inv[(start < w1) & (start + dur > w0)], minlength=len(keys))
        for k, sc, times in zip(keys, scopes, recorded):
            if sc and times:
                counts[k] = counts.get(k, 0) + int(times)
        for scope in frozenset().union(*scopes):
            sel = np.array([scope in sc for sc in scopes], bool)[inv]
            lo, hi = trace.busy_intervals(start[sel], dur[sel], t.window)
            secs[scope] = (secs.get(scope, 0.0)
                           + float((hi - lo).sum()) * 1e-9)
    steps = float(np.median(list(counts.values()))) if counts else 0.0
    return secs, steps


def scope_us_per_step(ctx, scope: str):
    """Device time (us) of the ops whose name stack holds `scope`, per
    recorded iteration of the scan body; None where no op holds it."""
    _, (secs, steps) = _reading(ctx)
    if secs.get(scope, 0.0) <= 0 or not 0 < steps <= ctx["events"]:
        return None
    return secs[scope] / steps * 1e6


def span_ms(ctx, name: str):
    """Wall time (ms) of the host spans called `name` inside the window;
    None where there is none."""
    x, _ = _reading(ctx)
    if x is None:
        return None
    lo, hi = ctx["trace"].window
    d = [e - s for n, s, e in x.spans if n == name and s >= lo and e <= hi]
    return sum(d) * 1e-6 if d else None


def idle_gaps(ctx, k: int = 10) -> list:
    """`bench.trace.idle_gaps` with the program's `m4.` spans among the
    labels: each gap is named by the innermost span open at its middle."""
    t = ctx["trace"]
    x, _ = _reading(ctx)
    return trace.idle_gaps(t if x is None else
                           dataclasses.replace(t, spans=x.spans), k)
