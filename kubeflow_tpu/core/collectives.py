"""Collective primitives over the mesh + micro-benchmarks.

The reference stack's collective layer is NCCL/Gloo/Horovod/MPI linked into
user containers (SURVEY.md §2.7); the platform never calls it, only wires it.
On TPU the collectives are XLA-emitted onto ICI, so this module is thin:
named-axis wrappers usable inside ``shard_map``/``pjit``-sharded code, ring
helpers for pipeline/context parallelism, and the psum/all_gather/ppermute/
all_to_all micro-benchmarks SURVEY.md §7 step 1 calls for (the
``hvd.allreduce`` → ``lax.psum`` mapping of BASELINE config 3).
"""

from __future__ import annotations

import time
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# --------------------------------------------------------------------------- #
# Named-axis wrappers. Inside shard_map/pjit these lower to single ICI
# collectives; they exist so call sites read like the strategy table in
# SURVEY.md §2.6 rather than raw lax.
# --------------------------------------------------------------------------- #

def grad_allreduce(grads, axis: str):
    """Mean-allreduce of gradients over a data axis — the DDP bucketed
    allreduce / ``hvd.allreduce`` analog, as one fused psum."""
    return jax.tree_util.tree_map(lambda g: lax.pmean(g, axis), grads)


def psum(x, axis: str):
    return lax.psum(x, axis)


def all_gather(x, axis: str, *, tiled: bool = True, gather_axis: int = 0):
    """FSDP param gather (ZeRO all-gather analog)."""
    return lax.all_gather(x, axis, axis=gather_axis, tiled=tiled)


def reduce_scatter(x, axis: str, *, scatter_axis: int = 0):
    """FSDP gradient reduce-scatter (ZeRO reduce-scatter analog)."""
    return lax.psum_scatter(x, axis, scatter_dimension=scatter_axis, tiled=True)


def ring_shift(x, axis: str, *, shift: int = 1):
    """Rotate shards around the axis ring with ``ppermute`` — the building
    block of ring attention (KV rotation) and pipeline stage handoff.
    ICI tori make each hop a physical-neighbor transfer."""
    n = lax.axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis, perm)


def all_to_all(x, axis: str, *, split_axis: int, concat_axis: int):
    """Re-shard between two tensor dimensions over ``axis`` — Ulysses
    sequence<->heads swap, MoE token dispatch."""
    return lax.all_to_all(x, axis, split_axis=split_axis, concat_axis=concat_axis, tiled=True)


# --------------------------------------------------------------------------- #
# Micro-benchmarks (SURVEY.md §7 step 1; BASELINE config 3's allreduce path).
# --------------------------------------------------------------------------- #

def _timed(fn: Callable[[], jax.Array], iters: int, warmup: int) -> float:
    for _ in range(warmup):
        fn().block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    out.block_until_ready()
    return (time.perf_counter() - t0) / iters


def benchmark_collective(
    mesh: Mesh,
    axis: str,
    kind: str = "psum",
    *,
    mb_per_shard: float = 4.0,
    dtype=jnp.float32,
    iters: int = 10,
    warmup: int = 3,
) -> dict:
    """Time one collective over ``axis``; returns sec/op and algo bandwidth.

    ``kind``: psum | all_gather | reduce_scatter | ppermute | all_to_all.
    Algo-bandwidth convention matches nccl-tests so numbers are comparable
    with the reference stack's NCCL/Horovod benchmarking practice.
    """
    n = mesh.shape[axis]
    elem = jnp.dtype(dtype).itemsize
    rows = max(int(mb_per_shard * 1e6 / (128 * elem)), n)
    rows -= rows % n  # all_to_all needs divisibility
    shard_shape = (rows, 128)
    nbytes = rows * 128 * elem

    ops = {
        "psum": lambda x: lax.psum(x, axis),
        "all_gather": lambda x: lax.all_gather(x, axis, axis=0, tiled=True),
        "reduce_scatter": lambda x: lax.psum_scatter(x, axis, scatter_dimension=0, tiled=True),
        "ppermute": lambda x: ring_shift(x, axis),
        "all_to_all": lambda x: lax.all_to_all(x, axis, split_axis=0, concat_axis=1, tiled=True),
    }
    if kind not in ops:
        raise ValueError(f"unknown collective {kind!r}; options {sorted(ops)}")
    op = ops[kind]

    spec = P(axis)  # shard rows over the axis
    @partial(
        jax.jit,
        in_shardings=NamedSharding(mesh, spec),
        out_shardings=NamedSharding(mesh, _out_spec(kind, axis)),
    )
    def step(x):
        # check_vma=False: all_gather output is replicated over `axis`, which
        # the static varying-manifest check can't always infer.
        return jax.shard_map(
            op, mesh=mesh, in_specs=spec, out_specs=_out_spec(kind, axis),
            check_vma=False,
        )(x)

    global_shape = (shard_shape[0] * n, shard_shape[1])
    x = jax.device_put(
        jnp.ones(global_shape, dtype), NamedSharding(mesh, spec)
    )
    sec = _timed(lambda: step(x), iters, warmup)

    # Algorithmic bytes moved per device (nccl-tests convention).
    factor = {
        "psum": 2 * (n - 1) / n,
        "all_gather": (n - 1) / n,
        "reduce_scatter": (n - 1) / n,
        "ppermute": 1.0,
        "all_to_all": (n - 1) / n,
    }[kind]
    busbw = nbytes * n * factor / sec if sec > 0 else float("inf")
    return {
        "kind": kind,
        "axis": axis,
        "axis_size": n,
        "bytes_per_shard": nbytes,
        "sec_per_op": sec,
        "bus_gbps": busbw / 1e9,
    }


def _out_spec(kind: str, axis: str) -> P:
    # all_gather returns replicated-along-axis output; everything else keeps
    # the input sharding layout.
    if kind == "all_gather":
        return P(None)
    return P(axis)


def benchmark_suite(mesh: Mesh, axis: str, **kw) -> list[dict]:
    return [
        benchmark_collective(mesh, axis, kind, **kw)
        for kind in ("psum", "all_gather", "reduce_scatter", "ppermute", "all_to_all")
    ]
