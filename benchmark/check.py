"""What decides a serving cell's ``correct``, as data of the cell.

A traffic file whose runner serves a model carries a ``check`` block and a
non-empty ``check_why`` beside it (the training runner's ``check`` is read
in ``runners/train_fit.py``)::

    "check": {
      "requests": 3,                      completed requests compared
      "regret_mean": 0.001,               mandatory
      "regret_max": 0.1,                  at least one of regret_max,
      "regret_p99": 0.02,                 regret_p99
      "argmax_share_min": 0.95,           optional
      "measured": {"regret_mean": 0.0003, "regret_max": 0.042},
      "seeds": [31, 37, 41, 43, 47]       the chip runs ``measured`` is from
    }

A *regret* is how far the float32 reference's logit of the token the engine
served lies below that position's best, in standard deviations of that
position's logits (``serve_common.regrets_of``). Every limit present must
hold. ``measured`` gives, under the same keys, the worst value the cell's
author read on the chip over ``seeds``; a limit looser than ``TIMES`` its
measured value is refused (for ``argmax_share_min``: a shortfall from 1 of
more than ``TIMES`` the measured one), and so is one looser than
``BACKSTOP`` whatever was measured. There is no default: a missing block, a
missing reason or an unknown key is an error before any request is sent.

Why a 99th percentile beside the maximum: a model with a discrete choice
inside (top-k routing) flips a near-tie under bf16 activations, and a
flipped position's logits move by tenths of a standard deviation, so an
honest model's maximum is a draw from a heavy tail (PERF.md section 4 has
the table). ``regret_p99`` is judged only on ``P99_MIN_TOKENS`` tokens or
more (ten samples beyond it); with fewer the run is not ``correct``.
"""

from __future__ import annotations

from typing import Any, Mapping

import numpy as np

from benchmark.stats import percentile

#: a limit is at most this many times the worst value measured on the chip
TIMES = 10.0
#: the loosest limits any cell may state. Loose on purpose: an honest
#: routed model that holds all of its experts reads mean 0.017, while
#: mathematics wrong throughout (the chosen experts' weights not normalised)
#: reads mean 1.1, p99 3.4, 17 % the reference's choice (PERF.md section 4).
#: p99: Trinity-Mini's sound runs read up to 0.4991 and its int8-weights
#: control at least 0.9576, so the cell's limit, 0.7, lies between them
BACKSTOP = {"regret_mean": 0.05, "regret_p99": 0.7, "argmax_share_min": 0.8}
P99_MIN_TOKENS = 1000

#: limit key -> the statistic it bounds
LIMITS = {
    "regret_max": "regret_max", "regret_p99": "regret_p99",
    "regret_mean": "regret_mean", "argmax_share_min": "argmax_share",
}
KEYS = {"requests", "measured", "seeds", *LIMITS}


def validate(mix: Mapping[str, Any]) -> dict[str, Any]:
    """The mix's ``check`` block, or ``ValueError`` naming what is wrong
    with it."""
    def bad(what: str):
        return ValueError(f"the traffic file's check block: {what}")

    check = mix.get("check")
    if not isinstance(check, Mapping):
        raise bad("missing; a serving cell states its own limits (benchmark/check.py)")
    if not str(mix.get("check_why") or "").strip():
        raise bad("needs a non-empty check_why beside it: the runs the limits were set from")
    if unknown := sorted(set(check) - KEYS):
        raise bad(f"unknown key(s) {unknown}; known: {sorted(KEYS)}")
    if not isinstance(check.get("requests"), int) or check["requests"] < 1:
        raise bad("requests must be a whole number of at least 1")
    if "regret_mean" not in check:
        raise bad("regret_mean is mandatory")
    if "regret_max" not in check and "regret_p99" not in check:
        raise bad("needs regret_max or regret_p99 beside regret_mean")
    seeds = check.get("seeds")
    if not isinstance(seeds, list) or len(set(seeds)) < 5:
        raise bad("seeds must list the five or more chip runs that measured was read on")
    measured = check.get("measured")
    limits = {k: check[k] for k in LIMITS if k in check}
    if not isinstance(measured, Mapping) or set(measured) != set(limits):
        raise bad(f"measured must give the worst chip reading of exactly {sorted(limits)}")
    for key, limit in limits.items():
        if not all(isinstance(v, (int, float)) for v in (limit, measured[key])):
            raise bad(f"{key} and its measured value must be numbers")
        # as distances from a perfect reading, so that smaller is tighter
        slack = (lambda v: 1.0 - v) if key.endswith("_min") else (lambda v: v)
        if slack(limit) < 0 or slack(measured[key]) < 0:
            raise bad(f"{key} {limit} (measured {measured[key]}) is outside its range")
        if slack(limit) > TIMES * slack(measured[key]):
            raise bad(f"{key} {limit} is looser than {TIMES:g} times the measured {measured[key]}")
        if key in BACKSTOP and slack(limit) > slack(BACKSTOP[key]):
            raise bad(f"{key} {limit} is looser than the backstop {BACKSTOP[key]}")
    return dict(check)


def judge(regrets, check: Mapping[str, Any]) -> dict[str, Any]:
    """The verdict on one run's regrets under a validated ``check`` block.
    ``numbers`` holds every statistic, each judged one with its limit
    beside it as ``<statistic>_limit``: what the result line carries."""
    r = np.asarray(regrets, np.float64).ravel()
    stats = {
        "regret_max": float(r.max()), "regret_p99": percentile(r.tolist(), 0.99),
        "regret_mean": float(r.mean()), "argmax_share": float((r == 0).mean()),
    }
    numbers: dict[str, float] = {"tokens_checked": int(r.size)}
    failed = []
    for key, stat in LIMITS.items():
        numbers[stat] = stats[stat]
        if key not in check:
            continue
        numbers[f"{stat}_limit"] = check[key]
        lower = key.endswith("_min")
        if stats[stat] < check[key] if lower else stats[stat] > check[key]:
            failed.append(f"{stat} {stats[stat]:.6g} against {key} {check[key]}")
    if "regret_p99" in check:
        numbers["tokens_checked_limit"] = P99_MIN_TOKENS
        if r.size < P99_MIN_TOKENS:
            failed.append(
                f"{r.size} tokens checked: regret_p99 needs {P99_MIN_TOKENS} (ten beyond it)"
            )
    return {"ok": not failed, "failed": failed, "numbers": numbers}


def stderr_lines(numbers: Mapping[str, float]) -> list[str]:
    """``check: <name> <value> limit <limit>`` for every number of a result
    line's ``check``: the run's last lines on standard error."""
    return [
        f"check: {name} {value!r}"
        + (f" limit {numbers[f'{name}_limit']!r}" if f"{name}_limit" in numbers else "")
        for name, value in numbers.items() if not name.endswith("_limit")
    ]
