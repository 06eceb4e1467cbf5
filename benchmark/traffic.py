"""One general traffic generator. A traffic mix is a JSON file of
parameters (``benchmark/traffic/<mix>.json``); everything drawn — lengths,
token ids, arrival times — comes from ``--seed``, never from a clock, so
the same seed offers the same load to any build of the program.

Lengths (``prompt_tokens``, ``output_tokens``): ``{"dist": "lognormal",
"median", "sigma", "min", "max"}``, clipped. Draws are *stratified*: every
block of ``BLOCK`` consecutive draws holds the ``BLOCK`` quantile midpoints
of the distribution, in an order the seed picks. Any seed therefore offers
the same multiset of lengths per block — the same amount of work — in a
different order; the marginal distribution is the one named.

Arrivals (``arrivals``, open loop): ``{"process": "poisson", "rate_rps"}``
— independent users, exponential gaps, not stratified: bursts and lulls
are what an open loop is for. Copied from the program's
``loadgen/arrivals.py`` (``PoissonArrivals``: seeded, the schedule a value).
"""

from __future__ import annotations

import dataclasses
import math
import random
import statistics
from typing import Any, Mapping

import numpy as np

#: draws per stratum block
BLOCK = 16
NORMAL = statistics.NormalDist()


def stratified_uniforms(n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` numbers in (0, 1): each block of ``BLOCK`` is the block's
    quantile midpoints ``(i + 0.5) / BLOCK`` in seeded order."""
    blocks = -(-n // BLOCK)
    mid = (np.arange(BLOCK) + 0.5) / BLOCK
    return np.concatenate([rng.permutation(mid) for _ in range(blocks)])[:n]


def draw_lengths(spec: Mapping[str, Any], n: int, rng: np.random.Generator) -> np.ndarray:
    return lengths_at(spec, stratified_uniforms(n, rng))


def lengths_at(spec: Mapping[str, Any], quantiles) -> np.ndarray:
    """The distribution's lengths at the given quantiles in (0, 1)."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.array([NORMAL.inv_cdf(x) for x in quantiles])
    raw = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def arrival_offsets(spec: Mapping[str, Any], duration_s: float, seed: int) -> list[float]:
    """Offsets (seconds from the start of the schedule) of every arrival
    in ``[0, duration_s)``."""
    if spec["process"] != "poisson":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    if duration_s <= 0:
        return []
    rate = spec["rate_rps"]
    rng = random.Random(f"{seed}:poisson")
    out, t = [], rng.expovariate(rate)
    while t < duration_s:
        out.append(t)
        t += rng.expovariate(rate)
    return out


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    prompt: tuple[int, ...]
    max_new_tokens: int
    #: open loop: offset from the start of the schedule; closed loop: 0
    due_s: float = 0.0


def make_requests(
    mix: Mapping[str, Any], vocab_size: int, seed: int, n: int,
    due: list[float] | None = None,
) -> list[Request]:
    """``n`` requests of the mix: uniform token ids in ``[2, vocab)`` (0 and
    1 are the program's pad and default eos), no two prompts sharing a
    prefix, lengths from the mix's distributions."""
    rng = np.random.default_rng([seed, 0x7AFF1C])
    p_len = draw_lengths(mix["prompt_tokens"], n, rng)
    o_len = draw_lengths(mix["output_tokens"], n, rng)
    return [
        Request(
            index=i,
            prompt=tuple(int(t) for t in rng.integers(2, vocab_size, size=int(p_len[i]))),
            max_new_tokens=int(o_len[i]),
            due_s=0.0 if due is None else due[i],
        )
        for i in range(n)
    ]


def token_batches(vocab_size: int, seq_len: int, batch: int, seed: int):
    """``start_step -> iterator`` of ``{"inputs", "targets"}`` batches of
    full-length sequences of uniform token ids (``Trainer.fit``'s data
    contract); batch ``i`` depends on ``(seed, i)`` alone."""

    def factory(start_step: int = 0):
        step = start_step
        while True:
            rng = np.random.default_rng([seed, 0xDA7A, step])
            toks = rng.integers(
                2, vocab_size, size=(batch, seq_len + 1), dtype=np.int32
            )
            yield {"inputs": toks[:, :-1], "targets": toks[:, 1:]}
            step += 1

    return factory
