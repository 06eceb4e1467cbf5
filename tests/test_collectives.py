"""core/collectives: numerics of each wrapper + the benchmark harness."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from kubeflow_tpu.core import collectives as coll
from kubeflow_tpu.core.mesh import Axis, MeshSpec, build_mesh


def _shmap(mesh, fn, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs)


def test_grad_allreduce_is_mean(devices8):
    mesh = build_mesh(MeshSpec.data_parallel(8))
    x = jnp.arange(8.0)

    out = _shmap(
        mesh,
        lambda x: coll.grad_allreduce({"g": x}, Axis.DATA)["g"],
        P(Axis.DATA),
        P(Axis.DATA),
    )(x)
    np.testing.assert_allclose(np.asarray(out), np.full(8, x.mean()), rtol=1e-6)


def test_ring_shift(devices8):
    mesh = build_mesh(MeshSpec.data_parallel(8))
    x = jnp.arange(8.0)
    out = _shmap(
        mesh, lambda x: coll.ring_shift(x, Axis.DATA), P(Axis.DATA), P(Axis.DATA)
    )(x)
    # shard i goes to shard i+1 → output shard j holds value (j-1) mod 8
    np.testing.assert_allclose(np.asarray(out), np.roll(np.arange(8.0), 1))


def test_all_gather_and_reduce_scatter_roundtrip(devices8):
    mesh = build_mesh(MeshSpec.fsdp_parallel(8))
    x = jnp.arange(16.0)

    def body(xs):
        full = coll.all_gather(xs, Axis.FSDP)  # (16,) on every shard
        return coll.reduce_scatter(full, Axis.FSDP)  # sum over 8 shards, rescattered

    out = _shmap(mesh, body, P(Axis.FSDP), P(Axis.FSDP))(x)
    np.testing.assert_allclose(np.asarray(out), np.arange(16.0) * 8)


def test_all_to_all_ulysses_swap(devices8):
    """seq-sharded → head-sharded and back (the Ulysses pattern)."""
    mesh = build_mesh(MeshSpec(seq=8))
    seq, heads, dim = 16, 8, 4
    x = np.random.RandomState(0).randn(seq, heads, dim).astype(np.float32)

    def body(xs):  # xs: (seq/8, heads, dim)
        ys = coll.all_to_all(xs, Axis.SEQ, split_axis=1, concat_axis=0)
        return coll.all_to_all(ys, Axis.SEQ, split_axis=0, concat_axis=1)

    out = _shmap(mesh, body, P(Axis.SEQ), P(Axis.SEQ))(jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(out), x, rtol=1e-6)


def test_benchmark_collective_runs(devices8):
    mesh = build_mesh(MeshSpec.data_parallel(8))
    r = coll.benchmark_collective(mesh, Axis.DATA, "psum", mb_per_shard=0.1, iters=2, warmup=1)
    assert r["sec_per_op"] > 0 and r["bus_gbps"] > 0 and r["axis_size"] == 8


def test_benchmark_suite_all_kinds(devices8):
    mesh = build_mesh(MeshSpec.data_parallel(8))
    rs = coll.benchmark_suite(mesh, Axis.DATA, mb_per_shard=0.05, iters=1, warmup=1)
    assert {r["kind"] for r in rs} == {"psum", "all_gather", "reduce_scatter", "ppermute", "all_to_all"}
