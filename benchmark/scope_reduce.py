"""Device time by part of the program: each operation of a profiler trace
named by the scope it was traced in.

``trace_reduce`` reads a trace through ``jax.profiler.ProfileData``, which
gives an operation's HLO text and times but not the stats of its metadata.
Read as the ``XSpace`` protobuf it is, the same file gives each ``XLA Ops``
event's ``tf_op``: the instruction's ``op_name``, the path of scopes it was
traced under (``jit(_chunk_paged_impl)/while/body/closed_call/TransformerLM/
layers_3/attn/q_proj/dot_general``; an operation on an argument carries the
argument's path instead, ``params['layers_3']['attn'][…]``). Flax's modules
and the program's own ``jax.named_scope`` parts (``kubeflow_tpu/core/
parts.py``) are the names a part is known by; which names make up which
part is data of the metric files (``benchmark/layer_metrics/``).

An operation's *program* is the ``XLA Modules`` event that contains it on
its chip (``jit__chunk_paged_impl(<fingerprint>)`` → ``jit__chunk_paged_impl``).
The ``jit(<function>)`` prefix of its ``tf_op`` names the same program, and
an operation where the two disagree is counted (``mismatched``): an inner
function's operations can carry a name that starts at that function
(``jit(searchsorted)/…`` inside a prefill piece), which names no program.
Where no module event contains an operation, the prefix names its program.
Times are self times inside the benchmark's ``bench.trace_window``
annotation, as ``trace_reduce`` computes them.

The protobuf classes are the installed ``tensorflow`` package's generated
``xplane_pb2``, loaded from its file: importing ``tensorflow`` itself takes
many seconds and may claim the chip.

    python3 -m benchmark.scope_reduce <trace.xplane.pb> [--program <regex>]

prints seconds and % by (program, part), and the unscoped rest by its
largest ``tf_op`` prefixes.
"""

from __future__ import annotations

import argparse
import bisect
import dataclasses
import importlib.util
import json
import os
import re
import resource
import sys
import time
from pathlib import Path
from typing import Mapping, Sequence

from benchmark import trace_reduce as tr
from benchmark.manifest import ROOT

#: the stat of an operation's metadata that holds its ``op_name``
TF_OP = "tf_op"
#: the metric file whose ``parts`` are the whole vocabulary
VOCABULARY = ROOT / "benchmark" / "layer_metrics" / "device_unscoped_share.json"

_PB2 = None


def xplane_pb2():
    """``tensorflow/tsl/profiler/protobuf/xplane_pb2.py`` as a module of its
    own: ``find_spec`` locates the package without importing it."""
    global _PB2
    if _PB2 is None:
        spec = importlib.util.find_spec("tensorflow")
        if spec is None or not spec.submodule_search_locations:
            raise ImportError("the XSpace protobuf classes come with tensorflow, not installed")
        path = os.path.join(
            spec.submodule_search_locations[0], "tsl", "profiler", "protobuf", "xplane_pb2.py"
        )
        module_spec = importlib.util.spec_from_file_location("_bench_xplane_pb2", path)
        module = importlib.util.module_from_spec(module_spec)
        module_spec.loader.exec_module(module)
        _PB2 = module
    return _PB2


@dataclasses.dataclass(frozen=True)
class Op:
    start: float   # seconds on the trace's clock
    end: float
    #: the instruction's name (``fusion.12``, ``copy-done.3``)
    name: str
    #: its ``op_name``, "" where the trace gives none
    tf_op: str


@dataclasses.dataclass
class ScopeTrace:
    #: chip index → operation events (``XLA Ops``)
    ops: dict[int, list[Op]]
    #: chip index → program events (``XLA Modules``), named as the trace does
    modules: dict[int, list[tr.Event]]
    #: the benchmark's window annotations on the host
    marks: list[tr.Event]


def _stat_value(stat, stat_names: Mapping[int, str]) -> str:
    kind = stat.WhichOneof("value")
    if kind == "ref_value":
        return stat_names.get(stat.ref_value, "")
    if kind == "bytes_value":
        return stat.bytes_value.decode(errors="replace")
    return str(getattr(stat, kind)) if kind else ""


def _span(line, event) -> tuple[float, float]:
    """Start and end in seconds, each from a whole number of picoseconds:
    an operation that starts where the one before it ends then compares
    equal to that end, not a rounding error inside it."""
    start = line.timestamp_ns * 1000 + event.offset_ps
    return start * 1e-12, (start + event.duration_ps) * 1e-12


def load(path: str | os.PathLike) -> ScopeTrace:
    space = xplane_pb2().XSpace()
    space.ParseFromString(Path(path).read_bytes())
    ops: dict[int, list[Op]] = {}
    modules: dict[int, list[tr.Event]] = {}
    marks: list[tr.Event] = []
    for plane in space.planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        if m is None and plane.name != tr.HOST_PLANE:
            continue
        names = {k: md.name for k, md in plane.event_metadata.items()}
        if m is None:
            for line in plane.lines:
                marks.extend(
                    tr.Event(tr.WINDOW_ANNOTATION, *_span(line, e))
                    for e in line.events if names.get(e.metadata_id) == tr.WINDOW_ANNOTATION
                )
            continue
        chip = int(m.group(1))
        stat_names = {k: sm.name for k, sm in plane.stat_metadata.items()}
        tf_op_ids = {k for k, name in stat_names.items() if name == TF_OP}

        def tf_op_of(stats) -> str | None:
            for s in stats:
                if s.metadata_id in tf_op_ids:
                    return _stat_value(s, stat_names)
            return None

        meta_tf_op = {k: tf_op_of(md.stats) or "" for k, md in plane.event_metadata.items()}
        short = {k: tr.short_name(name) for k, name in names.items()}
        for line in plane.lines:
            if line.name == tr.OP_LINE:
                out = ops.setdefault(chip, [])
                for e in line.events:
                    own = tf_op_of(e.stats) if len(e.stats) else None
                    out.append(Op(
                        *_span(line, e), short.get(e.metadata_id, ""),
                        own if own is not None else meta_tf_op.get(e.metadata_id, ""),
                    ))
            elif line.name == tr.MODULE_LINE:
                modules.setdefault(chip, []).extend(
                    tr.Event(names.get(e.metadata_id, ""), *_span(line, e)) for e in line.events
                )
    return ScopeTrace(ops, modules, marks)


def program_of_module(name: str) -> str:
    """``jit__chunk_paged_impl(123)`` → ``jit__chunk_paged_impl``."""
    return re.sub(r"\(.*$", "", name)


def program_of_tf_op(tf_op: str) -> str | None:
    """``jit(_chunk_paged_impl)/while/…`` → ``jit__chunk_paged_impl``, the
    name the module line gives the same program; None for an argument's
    path or a name without the prefix."""
    m = re.match(r"jit\(([^()]*)\)(/|$)", tf_op)
    return f"jit_{m.group(1)}" if m else None


def _compile(parts: Mapping[str, Sequence[str]]) -> list[tuple[str, re.Pattern]]:
    return [(part, re.compile(p)) for part, patterns in parts.items() for p in patterns]


def part_of(tf_op: str, compiled: Sequence[tuple[str, re.Pattern]]) -> str | None:
    """The part an operation belongs to: of the parts whose patterns match
    its ``tf_op``, the one matched furthest along the path — the innermost
    scope (``…/layers_3/attn/…`` is ``attn`` though ``layers_3`` matches
    too). None where no pattern matches."""
    best, at = None, -1
    for part, rx in compiled:
        for m in rx.finditer(tf_op):
            if m.start() > at:
                best, at = part, m.start()
    return best


@dataclasses.dataclass
class ScopeReduction:
    window: tuple[float, float]
    chips: int
    #: self seconds summed over chips by (program, tf_op, instruction group)
    seconds: dict[tuple[str, str, str], float]
    #: operations whose ``tf_op`` names another program than the one the
    #: module line runs around them
    mismatched: int
    events: int

    @property
    def busy_s(self) -> float:
        """Self seconds of every operation, summed over chips: each moment
        an operation ran, counted once."""
        return sum(self.seconds.values())

    def by_part(self, parts: Mapping[str, Sequence[str]]) -> dict[tuple[str, str | None], float]:
        """Self seconds by (program, part); part None is the unscoped rest."""
        compiled = _compile(parts)
        cache: dict[str, str | None] = {}
        out: dict[tuple[str, str | None], float] = {}
        for (prog, tf_op, _), s in self.seconds.items():
            if tf_op not in cache:
                cache[tf_op] = part_of(tf_op, compiled)
            key = (prog, cache[tf_op])
            out[key] = out.get(key, 0.0) + s
        return out

    def unscoped(self, parts: Mapping[str, Sequence[str]], n: int = 10) -> list[list]:
        """The largest groups of the unscoped rest: by ``tf_op`` with its
        last component (the operation) removed, or by instruction where the
        trace gives no ``tf_op``."""
        compiled = _compile(parts)
        groups: dict[str, float] = {}
        for (_, tf_op, group), s in self.seconds.items():
            if part_of(tf_op, compiled) is None:
                key = tf_op.rsplit("/", 1)[0] if tf_op else f"<no tf_op> {group}"
                groups[key] = groups.get(key, 0.0) + s
        return [[k, s] for k, s in sorted(groups.items(), key=lambda kv: -kv[1])[:n]]


def reduce(trace: ScopeTrace, window: tuple[float, float] | None = None) -> ScopeReduction:
    """The window is, as in ``trace_reduce.reduce``, the benchmark's own
    annotation where the trace has one, else first to last operation."""
    if not any(trace.ops.values()):
        raise ValueError("the trace holds no device operation")
    if window is None:
        if trace.marks:
            window = (min(e.start for e in trace.marks), max(e.end for e in trace.marks))
        else:
            window = (
                min(o.start for ops in trace.ops.values() for o in ops),
                max(o.end for ops in trace.ops.values() for o in ops),
            )
    lo, hi = window
    seconds: dict[tuple[str, str, str], float] = {}
    mismatched = events = 0
    for chip, chip_ops in trace.ops.items():
        modules = sorted(trace.modules.get(chip, []), key=lambda e: e.start)
        starts = [e.start for e in modules]
        inside = [o for o in chip_ops if o.end > lo and o.start < hi]
        # self times through trace_reduce: the event's name indexes ``inside``
        clipped = [
            tr.Event(str(i), max(o.start, lo), min(o.end, hi)) for i, o in enumerate(inside)
        ]
        for e, s in tr.self_times(clipped):
            o = inside[int(e.name)]
            k = bisect.bisect_right(starts, o.start) - 1
            program = (
                program_of_module(modules[k].name)
                if k >= 0 and modules[k].end >= o.end - 1e-9 else None
            )
            named = program_of_tf_op(o.tf_op)
            if program is not None and named is not None and named != program:
                mismatched += 1
            program = program or named or ""
            key = (program, o.tf_op, re.sub(r"(\.\d+)+$", "", o.name))
            seconds[key] = seconds.get(key, 0.0) + s
            events += 1
    return ScopeReduction(window, len(trace.ops), seconds, mismatched, events)


def vocabulary(path: Path = VOCABULARY) -> dict[str, list[str]]:
    return json.loads(Path(path).read_text())["parts"]


def describe(r: ScopeReduction, parts: Mapping[str, Sequence[str]], program: str | None = None) -> dict:
    """Seconds (averaged over chips) and % of each program's self time by
    part, the unscoped rest's largest groups, and the whole's shares."""
    chips = max(r.chips, 1)
    rx = re.compile(program) if program else None
    rows: dict[str, dict] = {}
    for (prog, part), s in sorted(r.by_part(parts).items(), key=lambda kv: -kv[1]):
        if rx is not None and not rx.search(prog):
            continue
        row = rows.setdefault(prog, {"seconds": 0.0, "parts": {}})
        row["seconds"] += s / chips
        row["parts"][part or "(unscoped)"] = s / chips
    for row in rows.values():
        row["parts"] = {
            part: {"seconds": s, "percent": 100.0 * s / row["seconds"]}
            for part, s in row["parts"].items()
        }
    busy = r.busy_s
    unscoped = sum(s for (_, part), s in r.by_part(parts).items() if part is None)
    return {
        "window_s": r.window[1] - r.window[0], "chips": r.chips, "events": r.events,
        "busy_s": busy / chips, "mismatched": r.mismatched,
        "unscoped_percent_of_busy": 100.0 * unscoped / busy if busy else None,
        "programs": dict(sorted(rows.items(), key=lambda kv: -kv[1]["seconds"])),
        "unscoped_top": [[k, s / chips] for k, s in r.unscoped(parts)],
    }


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("xplane")
    ap.add_argument("--program", help="a regular expression over program names")
    ap.add_argument("--parts", default=str(VOCABULARY),
                    help="a metric file whose 'parts' name the vocabulary")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    r = reduce(load(args.xplane))
    out = describe(r, vocabulary(Path(args.parts)), args.program)
    out["parse_s"] = time.perf_counter() - t0
    out["peak_rss_mb"] = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
