"""Reduction of a profiler trace (``.xplane.pb``) to device time.

Device operations are the events of each TPU plane's ``XLA Ops`` line
(named by their HLO instruction text; ``Async XLA Ops`` repeats the
spans of asynchronous pairs and is not read);
where there is no TPU plane (a trace recorded on the CPU), they are the
host events that carry an ``hlo_op`` stat, grouped by ``device_ordinal``.
Each operation gets its HLO name, its program, and its JAX name stack
(``op_name``), read from the event's stats where the trace has it, else
from the optimized HLO text of the program that ran it.

``summarize`` clips every device's operations to a window given by a
host annotation, takes the union of their intervals as busy time, sums
time by group (a predicate on the operation), and labels each idle gap
with the innermost host annotation that was open at its middle.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Callable, Dict, List, Optional

#: HLO op names of collectives (async ``-start``/``-done`` halves too)
COLLECTIVES = ("all-to-all", "all-gather", "all-reduce", "collective-permute",
               "reduce-scatter", "collective-broadcast", "all_to_all",
               "all_gather", "all_reduce", "ppermute", "reduce_scatter",
               "psum")

_EVENT_NAME = re.compile(r'^%?([\w.\-]+)\s*=')
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*.*?'
                    r'metadata=\{[^}]*?op_name="([^"]*)"')


def hlo_op_names(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: op_name}`` from optimized HLO text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


@dataclasses.dataclass
class Op:
    """One device operation of the trace."""
    device: str
    name: str
    start_ns: float
    dur_ns: float
    module: str
    scope: str

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    def is_collective(self) -> bool:
        return self.name.startswith(COLLECTIVES)


@dataclasses.dataclass
class Span:
    """A host annotation (``jax.profiler.TraceAnnotation``)."""
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under a profiler output directory."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def _stats(event) -> dict:
    try:
        return dict(event.stats)
    except (TypeError, ValueError):
        return {}


def load(path: str, hlo_names: Optional[Dict[str, Dict[str, str]]] = None):
    """``(ops, spans)`` of a trace file.  ``hlo_names`` maps a program
    name to its :func:`hlo_op_names`, for traces whose events carry no
    name stack."""
    from jax.profiler import ProfileData
    hlo_names = hlo_names or {}
    data = ProfileData.from_file(path)
    ops: List[Op] = []
    spans: List[Span] = []
    tpu = {p.name for p in data.planes if p.name.startswith("/device:TPU:")
           and "Core" not in p.name}
    for plane in data.planes:
        for line in plane.lines:
            if plane.name.startswith("/host:"):
                spans += [Span(e.name, e.start_ns, e.duration_ns)
                          for e in line.events if e.duration_ns > 0]
            on_device = plane.name in tpu and line.name == "XLA Ops"
            for e in line.events:
                st = _stats(e) if (on_device or not tpu) else {}
                if not on_device and (tpu or "hlo_op" not in st):
                    continue
                name = str(st.get("hlo_op", e.name))
                m = _EVENT_NAME.match(name)
                if m:           # TPU ops are named by their HLO text
                    name = m.group(1)
                module = str(st.get("hlo_module", ""))
                scope = str(st.get("tf_op", ""))
                if not scope:
                    scope = _lookup(hlo_names, module, name)
                device = (plane.name if on_device
                          else f"cpu:{st.get('device_ordinal', 0)}")
                ops.append(Op(device, name, e.start_ns, e.duration_ns,
                              module, scope))
    return ops, spans


def _lookup(hlo_names, module: str, name: str) -> str:
    if module in hlo_names:
        return hlo_names[module].get(name, "")
    for names in hlo_names.values():
        if name in names:
            return names[name]
    return ""


def union_ns(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _gaps(intervals, lo: float, hi: float):
    """Idle ``(start, end)`` gaps of ``[lo, hi]`` not covered."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def self_times(events) -> List[float]:
    """Each ``(start, end, op)``'s duration less that of the events that
    nest inside it (a loop and its body's operations)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    own = [e - s for s, e, _ in events]
    stack: List[int] = []
    for i in order:
        s, e, _ = events[i]
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= events[stack[-1]][1]:
            own[stack[-1]] -= e - s
        stack.append(i)
    return own


@dataclasses.dataclass
class Summary:
    """Device time of a traced window, averaged over its devices."""
    window_s: float
    busy_s: float
    n_devices: int
    groups_s: Dict[str, float]
    top_ops: List[list]
    idle_gaps: List[list]

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)


def summarize(ops: List[Op], spans: List[Span], window: str,
              groups: Dict[str, Callable[[Op], bool]], top: int = 10,
              label: Callable[[Op], str] = lambda op: op.name) -> Summary:
    """Busy time, grouped device time, the top operations (named by
    ``label``) and the longest idle gaps within the host annotation named
    ``window``."""
    win = [s for s in spans if s.name == window]
    if not win:
        raise ValueError(f"no host annotation {window!r} in the trace")
    lo, hi = win[0].start_ns, win[0].end_ns
    by_dev = defaultdict(list)
    for op in ops:
        s, e = max(op.start_ns, lo), min(op.end_ns, hi)
        if e > s:
            by_dev[op.device].append((s, e, op))
    if not by_dev:
        raise ValueError("no device operation inside the traced window")
    n = len(by_dev)
    busy = sum(union_ns([(s, e) for s, e, _ in v])
               for v in by_dev.values()) / n
    # a loop's event spans its body's events, so time is a union per
    # group, and an operation's own time excludes what nests inside it
    sums = {g: sum(union_ns([(s, e) for s, e, op in v if pred(op)])
                   for v in by_dev.values()) / n
            for g, pred in groups.items()}
    per_op = defaultdict(float)
    for v in by_dev.values():
        for (s, e, op), own in zip(v, self_times(v)):
            per_op[label(op)] += own / n
    inner = [s for s in spans if s.name != window
             and s.end_ns > lo and s.start_ns < hi]
    gaps = []
    for v in by_dev.values():
        for s, e in _gaps([(a, b) for a, b, _ in v], lo, hi):
            mid = (s + e) / 2
            open_ = [p for p in inner if p.start_ns <= mid <= p.end_ns]
            host = (min(open_, key=lambda p: p.dur_ns).name if open_
                    else "no host annotation")
            gaps.append([host, (e - s) / 1e9])
    gaps.sort(key=lambda g: -g[1])
    tops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return Summary(window_s=(hi - lo) / 1e9, busy_s=busy / 1e9,
                   n_devices=n, groups_s={g: v / 1e9 for g, v in sums.items()},
                   top_ops=[[k, v / 1e9] for k, v in tops],
                   idle_gaps=gaps[:top])
