"""From a profiler trace (``.xplane.pb``) to numbers: device busy time and
idle share, time by operation and by name pattern, collective time and its
exposed part, and the idle gaps labelled by what the host was doing.

Read with ``jax.profiler.ProfileData`` and nothing else. What a trace looks
like on a TPU v5e (looked at by hand, PR 22): one plane per chip named
``/device:TPU:<n>``; on it a line ``XLA Ops`` with one event per executed
HLO operation, named by the instruction's whole text (``%fusion.7 = f32[…]
fusion(…), kind=kOutput, calls=…``: here the *name* is ``fusion.7`` and the
text is kept as the *detail* that patterns search). Control-flow operations
such as ``while`` contain their bodies' events, so times here are *self*
times: an event's duration minus the events nested in it. A Pallas kernel is
a ``custom-call`` named after the scope it was traced in (the flash kernels
of a flax module ``attn`` are ``attn.42``…). ``XLA Modules`` has one event
per executed program (``jit_step(<fingerprint>)``), ``Steps`` one per step;
``Async XLA Ops`` repeats the asynchronous copies with their full duration. Host threads are lines of ``/host:CPU``;
``jax.profiler.TraceAnnotation`` spans appear there under their names.
Device and host events share one clock.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Iterable, Mapping, Sequence

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
#: the benchmark's own annotation around the traced part of the window
WINDOW_ANNOTATION = "bench.trace_window"
#: idle gaps per chip that get a host label (the longest ones)
MAX_LABELLED = 256

#: HLO collectives, as the trace names them (``all-reduce.7``,
#: ``all-gather-start.3`` ...). ``-start`` issues an asynchronous
#: collective and ``-done`` waits for it; data moves in between while
#: other operations run.
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast|ragged-all-to-all)(-start|-done)?(\.|$)"
)


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float   # seconds on the trace's clock
    end: float
    #: the instruction's whole text, where the trace gives one
    detail: str = ""

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    """A trace cut down to what the reduction reads."""

    #: chip index → operation events (the ``XLA Ops`` line)
    device_ops: dict[int, list[Event]]
    #: chip index → program events (the ``XLA Modules`` line)
    device_modules: dict[int, list[Event]]
    #: every event of every host thread
    host: list[Event]


def newest_xplane(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(paths, key=os.path.getmtime)


def load(profile) -> Trace:
    """``profile``: a path to an ``.xplane.pb`` or a ``ProfileData``."""
    if isinstance(profile, (str, os.PathLike)):
        from jax.profiler import ProfileData

        profile = ProfileData.from_file(str(profile))
    ops: dict[int, list[Event]] = {}
    modules: dict[int, list[Event]] = {}
    host: list[Event] = []

    def events(line) -> list[Event]:
        out = []
        for e in line.events:
            name = short_name(e.name)
            out.append(Event(
                name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                e.name if name != e.name else "",
            ))
        return out

    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name == OP_LINE:
                    ops.setdefault(chip, []).extend(events(line))
                elif line.name == MODULE_LINE:
                    modules.setdefault(chip, []).extend(events(line))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(events(line))
    return Trace(ops, modules, host)


# --------------------------------------------------------------------- #
# interval arithmetic
# --------------------------------------------------------------------- #

def union(intervals: Iterable[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merge overlapping or touching intervals; sorted, disjoint."""
    out: list[list[float]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals: Iterable[tuple[float, float]]) -> float:
    return sum(b - a for a, b in intervals)


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def complement(busy: Sequence[tuple[float, float]], lo: float, hi: float):
    """The gaps of a sorted disjoint ``busy`` inside ``[lo, hi]``."""
    gaps, at = [], lo
    for a, b in busy:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def self_times(events: Sequence[Event]) -> list[tuple[Event, float]]:
    """Each event with its self time: its duration minus the events nested
    directly inside it (a ``while`` contains its body's operations)."""
    order = sorted(events, key=lambda e: (e.start, -e.end))
    selfs = [e.dur for e in order]
    stack: list[int] = []
    for i, e in enumerate(order):
        while stack and order[stack[-1]].end <= e.start:
            stack.pop()
        if stack:
            selfs[stack[-1]] -= e.dur
        stack.append(i)
    return [(e, max(s, 0.0)) for e, s in zip(order, selfs)]


# --------------------------------------------------------------------- #
# the reduction
# --------------------------------------------------------------------- #

@dataclasses.dataclass
class Reduction:
    window: tuple[float, float]
    window_s: float
    #: per chip: seconds in which an operation ran, inside the window
    busy_s_by_chip: dict[int, float]
    #: self seconds by operation name, summed over chips
    op_seconds: dict[str, float]
    #: operation name → the instruction's text (what patterns search)
    op_detail: dict[str, str]
    #: per chip, disjoint idle gaps inside the window
    gaps_by_chip: dict[int, list[tuple[float, float]]]
    host: list[Event]
    ops_by_chip: dict[int, list[Event]]
    modules_by_chip: dict[int, list[Event]]

    @property
    def chips(self) -> int:
        return len(self.busy_s_by_chip)

    @property
    def busy_s(self) -> float:
        """Averaged over the chips used."""
        return sum(self.busy_s_by_chip.values()) / max(self.chips, 1)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def pattern_seconds(self, patterns: Sequence[str]) -> float:
        """Self seconds (summed over chips) of operations whose name
        matches any of ``patterns`` (regular expressions, searched)."""
        rx = [re.compile(p) for p in patterns]
        return sum(
            s for name, s in self.op_seconds.items()
            if any(r.search(self.op_detail.get(name) or name) for r in rx)
        )

    def top_ops(self, n: int = 10) -> list[list]:
        ranked = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])
        return [[name, s] for name, s in ranked[:n]]

    def top_op_groups(self, n: int = 10) -> list[list]:
        """Self seconds (averaged over chips) by operation with XLA's
        numbering removed — ``fusion``, ``convolution_add_fusion``, ``attn``
        (a kernel's scope), ``all-gather-start`` … — with how many
        instructions each group holds."""
        groups: dict[str, list[float]] = {}
        for name, s in self.op_seconds.items():
            groups.setdefault(re.sub(r"(\.\d+)+$", "", name), []).append(s)
        ranked = sorted(groups.items(), key=lambda kv: -sum(kv[1]))
        chips = max(self.chips, 1)
        return [[f"{base} x{len(v)}", sum(v) / chips] for base, v in ranked[:n]]

    def idle_gaps_by_host_activity(self, n: int = 10) -> list[list]:
        """Idle seconds (averaged over chips) grouped by the host event
        open at the middle of each gap — the innermost one — largest
        first. Only the ``MAX_LABELLED`` longest gaps of a chip are looked
        up; the rest are summed under one label."""
        import numpy as np

        # the window's own annotation covers every gap and explains none
        host = [e for e in self.host if e.name != WINDOW_ANNOTATION]
        starts = np.array([e.start for e in host])
        ends = np.array([e.end for e in host])
        by_label: dict[str, float] = {}
        for gaps in self.gaps_by_chip.values():
            ranked = sorted(gaps, key=lambda g: g[0] - g[1])
            for a, b in ranked[:MAX_LABELLED]:
                mid = 0.5 * (a + b)
                cover = np.flatnonzero((starts <= mid) & (ends > mid))
                if len(cover):
                    i = cover[np.argmin(ends[cover] - starts[cover])]
                    label = host[i].name
                else:
                    label = "no host event"
                by_label[label] = by_label.get(label, 0.0) + (b - a)
            rest = total(ranked[MAX_LABELLED:])
            if rest:
                by_label["shorter gaps, not looked up"] = (
                    by_label.get("shorter gaps, not looked up", 0.0) + rest
                )
        chips = max(self.chips, 1)
        ranked = sorted(by_label.items(), key=lambda kv: -kv[1])
        return [[name, s / chips] for name, s in ranked[:n]]

    def collective_seconds(self) -> tuple[float, float]:
        """``(in_flight, exposed)`` seconds, averaged over chips.

        *In flight*: the union of the intervals in which a collective was
        running — a synchronous collective's own event, or from an
        asynchronous one's ``-start`` to its ``-done``. *Exposed*: the part
        of that in which the chip ran nothing else — the collective events'
        own time on the operation line (issue, wait and synchronous
        transfers), since operations on that line run one at a time."""
        lo, hi = self.window
        in_flight = exposed = 0.0
        for chip, events in self.ops_by_chip.items():
            spans, own = [], []
            pending: dict[str, list[float]] = {}
            for e in sorted(events, key=lambda e: e.start):
                m = COLLECTIVE.match(e.name)
                if not m:
                    continue
                kind, phase = m.group(1), m.group(2)
                own.append((e.start, e.end))
                if phase == "-start":
                    pending.setdefault(kind, []).append(e.start)
                elif phase == "-done" and pending.get(kind):
                    spans.append((pending[kind].pop(0), e.end))
                else:
                    spans.append((e.start, e.end))
            in_flight += total(clip(union(spans), lo, hi))
            exposed += total(clip(union(own), lo, hi))
        chips = max(self.chips, 1)
        return in_flight / chips, exposed / chips

    def module_events(self, pattern: str) -> dict[int, list[Event]]:
        rx = re.compile(pattern)
        lo, hi = self.window
        return {
            chip: [e for e in evs if rx.search(e.name) and e.start >= lo and e.end <= hi]
            for chip, evs in self.modules_by_chip.items()
        }


def reduce(trace: Trace) -> Reduction:
    """The window is the benchmark's own ``bench.trace_window`` annotation
    where the trace has one, and otherwise from the first device event to
    the last."""
    marks = [e for e in trace.host if e.name == WINDOW_ANNOTATION]
    all_ops = [e for evs in trace.device_ops.values() for e in evs]
    if not all_ops:
        raise ValueError("the trace holds no device operation")
    if marks:
        lo, hi = marks[0].start, marks[-1].end
    else:
        lo = min(e.start for e in all_ops)
        hi = max(e.end for e in all_ops)
    busy_by_chip, gaps_by_chip = {}, {}
    op_seconds: dict[str, float] = {}
    op_detail: dict[str, str] = {}
    for chip, events in trace.device_ops.items():
        busy = clip(union((e.start, e.end) for e in events), lo, hi)
        busy_by_chip[chip] = total(busy)
        gaps_by_chip[chip] = complement(busy, lo, hi)
        inside = [
            Event(e.name, max(e.start, lo), min(e.end, hi), e.detail)
            for e in events if e.end > lo and e.start < hi
        ]
        for e, s in self_times(inside):
            op_seconds[e.name] = op_seconds.get(e.name, 0.0) + s
            op_detail.setdefault(e.name, e.detail)
    return Reduction(
        window=(lo, hi), window_s=hi - lo, busy_s_by_chip=busy_by_chip,
        op_seconds=op_seconds, op_detail=op_detail, gaps_by_chip=gaps_by_chip,
        host=trace.host,
        ops_by_chip=trace.device_ops, modules_by_chip=trace.device_modules,
    )


def short_name(text: str) -> str:
    """``%fusion.7 = f32[8]{0} fusion(…)`` → ``fusion.7``; other names
    (host events, programs) are kept whole."""
    head, sep, _ = text.partition(" = ")
    return head.lstrip("%") if sep and head.startswith("%") else text


def describe(path: str, top: int = 25) -> dict:
    """What a trace holds, for looking at one by hand: planes, lines, event
    counts, a few events of each line with their stats, and the reduction's
    headline numbers."""
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(path)
    planes = []
    for plane in profile.planes:
        lines = []
        for line in plane.lines:
            events = list(line.events)
            lines.append({
                "line": line.name, "events": len(events),
                "first": [
                    {"name": e.name[:120], "dur_us": e.duration_ns / 1e3,
                     "stats": {k: str(v)[:160] for k, v in list(e.stats)[:12]}}
                    for e in events[:2]
                ],
            })
        planes.append({"plane": plane.name, "lines": lines})
    out = {"planes": planes}
    try:
        r = reduce(load(profile))
    except ValueError as e:
        out["reduction"] = str(e)
        return out
    in_flight, exposed = r.collective_seconds()
    out["reduction"] = {
        "window_s": r.window_s, "busy_s": r.busy_s, "idle_share": r.idle_share,
        "chips": r.chips, "collective_in_flight_s": in_flight,
        "collective_exposed_s": exposed, "top_ops": r.top_ops(top),
        "top_op_groups": r.top_op_groups(top),
        "idle_gaps": r.idle_gaps_by_host_activity(10),
        "modules": {
            name: len(evs) for name, evs in _by_name(r.modules_by_chip).items()
        },
    }
    return out


def _by_name(by_chip: Mapping[int, Sequence[Event]]) -> dict[str, list[Event]]:
    out: dict[str, list[Event]] = {}
    for events in by_chip.values():
        for e in events:
            out.setdefault(e.name, []).append(e)
    return out


if __name__ == "__main__":
    import json
    import sys

    print(json.dumps(describe(sys.argv[1]), indent=1))
