"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

* device ops: the events of the ``XLA Ops`` line of each TPU device plane.
  An event is named by its HLO instruction (``%name.N = type op(...)``).
  Control-flow ops (``while``) appear there too, spanning the ops of their
  bodies; only the leaves, the ops that contain no other, count below;
* the traced window: the host annotation ``pb.window``;
* busy: the union of the leaf ops' intervals inside the window, averaged
  over the devices; idle share = 1 - busy / window;
* kernel calls: leaf ops whose HLO text matches a pattern, with the
  (B, n, m) stack read from the call's f32 output shape;
* idle gaps: the gaps between leaf ops inside the window, each labelled
  with the innermost ``pb.<span>`` open on the host at its midpoint.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

OPS_LINE = "XLA Ops"
WINDOW = "pb.window"
SHAPE = re.compile(r" = f32\[(\d+),(\d+),(\d+)\]")


@dataclass
class Op:
    text: str       # the HLO instruction
    start: int      # ns
    dur: int        # ns

    @property
    def name(self) -> str:
        """``%name.N`` and the output type, without the operands."""
        head, _, rest = self.text.partition(" = ")
        return f"{head} {rest.split('{')[0].split(' ')[0]}".strip()


@dataclass
class Trace:
    window: tuple[int, int] | None
    devices: list[list[Op]] = field(default_factory=list)
    spans: list[tuple[str, int, int]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def ops_in_window(self, ops: list[Op]) -> list[Op]:
        a, b = self.window
        return [o for o in ops if o.start >= a and o.start + o.dur <= b]


def _leaves(ops: list[Op]) -> list[Op]:
    """Ops that contain no other op (ops are sorted by start; times are
    whole nanoseconds, so a neighbour may overlap by one)."""
    out = []
    for i, o in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        end = o.start + o.dur
        if nxt is None or nxt.start >= end - 1 or nxt.start + nxt.dur > end + 1:
            out.append(o)
    return out


def load(path: str) -> Trace:
    """Device ops, host spans and the window of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    tr = Trace(window=None)
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    ops.append(Op(e.name, int(e.start_ns), int(e.duration_ns)))
            if ops:
                tr.devices.append(_leaves(sorted(ops, key=lambda o: o.start)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("pb."):
                        s = (e.name, int(e.start_ns),
                             int(e.start_ns + e.duration_ns))
                        tr.spans.append(s)
                        if e.name == WINDOW:
                            tr.window = (s[1], s[2])
    return tr


def busy_ns(tr: Trace, ops: list[Op]) -> int:
    """Length of the union of the ops' intervals inside the window."""
    total, end = 0, None
    for o in tr.ops_in_window(ops):
        a, b = o.start, o.start + o.dur
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def busy_s(tr: Trace) -> float:
    """Busy seconds in the window, averaged over the devices."""
    if not tr.devices or tr.window is None:
        return 0.0
    return sum(busy_ns(tr, d) for d in tr.devices) / len(tr.devices) * 1e-9


def kernel_calls(tr: Trace, pattern: str) -> list[tuple[float, int]]:
    """(seconds, B) of each call in the window whose HLO text matches."""
    rx = re.compile(pattern)
    out = []
    for ops in tr.devices:
        for o in tr.ops_in_window(ops):
            if rx.search(o.text):
                shape = SHAPE.search(o.text)
                if shape:
                    out.append((o.dur * 1e-9, int(shape.group(1))))
    return out


def top_ops(tr: Trace, k: int = 10) -> list[list]:
    """The k device ops that took most time in the window, by name."""
    tot: dict[str, int] = {}
    for ops in tr.devices:
        for o in tr.ops_in_window(ops):
            tot[o.name] = tot.get(o.name, 0) + o.dur
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns * 1e-9 / max(1, len(tr.devices))] for name, ns in top]


def _label(tr: Trace, t: int) -> str:
    best = None
    for name, a, b in tr.spans:
        if name != WINDOW and a <= t <= b and (best is None or a >= best[1]):
            best = (name, a)
    return best[0][3:] if best else "outside spans"


def idle_gaps(tr: Trace, k: int = 10) -> list[list]:
    """The k longest gaps between device ops in the window, labelled."""
    if not tr.devices or tr.window is None:
        return []
    gaps = []
    for ops in tr.devices[:1]:
        a, b = tr.window
        cursor = a
        for o in tr.ops_in_window(ops):
            if o.start > cursor:
                gaps.append((o.start - cursor, cursor))
            cursor = max(cursor, o.start + o.dur)
        if b > cursor:
            gaps.append((b - cursor, cursor))
    gaps.sort(reverse=True)
    return [[_label(tr, c + g // 2), g * 1e-9] for g, c in gaps[:k]]
