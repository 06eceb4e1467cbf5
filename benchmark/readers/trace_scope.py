"""Device time by part of the program (``benchmark/scope_reduce.py``): the
traced run's ``.xplane.pb`` read once more, for each operation's ``tf_op``.
The metric's file names the parts, ``{"parts": {<part>: [<regex over
tf_op>, ...]}}``, and what to read:

- ``"what": "program_share"``: % of the self time of the programs matching
  ``"program"`` (a regular expression over the module line's names,
  ``jit__chunk_paged_impl``) spent in operations of those parts;
- ``"what": "unscoped_share"``: % of the device's busy time (every chip)
  in operations that match no part.

The trace is parsed once per run, whatever number of metrics read it. No
traced run, no trace file, or no time in the program reads as nothing."""

from __future__ import annotations

import os
import re

from benchmark import harness, scope_reduce, trace_reduce

_PARSED: dict[tuple[str, float], scope_reduce.ScopeReduction] = {}


def reduction(ev) -> scope_reduce.ScopeReduction | None:
    if ev.trace is None:
        return None
    try:
        path = trace_reduce.newest_xplane(str(harness.OUT_DIR / "trace" / ev.cell["name"]))
    except FileNotFoundError:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _PARSED:
        _PARSED.clear()
        _PARSED[key] = scope_reduce.reduce(scope_reduce.load(path))
    return _PARSED[key]


def read(params, ev):
    r = reduction(ev)
    if r is None:
        return None
    split = r.by_part(params["parts"])
    what = params["what"]
    if what == "program_share":
        rx = re.compile(params["program"])
        mine = {key: s for key, s in split.items() if rx.search(key[0])}
        total = sum(mine.values())
        if total <= 0:
            return None
        return 100.0 * sum(s for (_, part), s in mine.items() if part is not None) / total
    if what == "unscoped_share":
        busy = sum(split.values())
        if busy <= 0:
            return None
        return 100.0 * sum(s for (_, part), s in split.items() if part is None) / busy
    raise ValueError(f"unknown trace_scope reading {what!r}")
