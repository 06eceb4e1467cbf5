"""Grouped matrix product: the rows of ``lhs`` lie sorted in groups, and
group ``g``'s rows are multiplied by ``rhs[g]`` — the experts' products of
a dropless expert layer (`parallel/expert.py::dropless_moe_ffn`), linear in
the rows whatever the experts' loads.

    out[r] = lhs[r] @ rhs[g]   for the rows r of group g

One path, chosen by what the call can observe (as `flash_tuning.
select_geometry` chooses a flash kernel's blocks): on a TPU, or under the
interpreter where a test asks for it, and with no mesh in force (a Mosaic
kernel is not partitioned automatically), the Pallas kernel below with a
tiling from the call's shape (:func:`select_gmm_tiling`); elsewhere
``jax.lax.ragged_dot``, XLA's own.

The kernel walks the schedule of the grouped-matmul kernel that ships with
JAX (``jax.experimental.pallas.ops.tpu.megablox``, whose
``make_group_metadata`` it calls): the rows are cut into tiles of ``tm``,
and a grid step is one (group, row tile) pair that share rows — at most
``m / tm + groups - 1`` of them, the number found from the group sizes at
run time, so a group with no row costs nothing and **a group's matrix
leaves HBM once** however few rows it has (a block whose index repeats from
one step to the next is not fetched again). Rows of a tile that belong to
another group are masked at the store. What is its own: a step takes the
whole ``(k, n)`` matrix where it fits (a decode step's product is bound by
reading each touched expert's matrix once: fewer, larger steps), and the
call's **name in a device trace states its shape**,
``moe_gmm_m<rows>_k<k>_n<n>_t<tm>x<tk>x<tn>``, so that the trace reduction
gives it a row and a metric can tell the decode call (``m`` = rows x
experts per token) from a prefill piece's. Candidates' readings on the
chip: PERF.md section 6, PR 34.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: bytes of one staged ``(tk, tn)`` block of ``rhs`` (double-buffered by the
#: pipeline): a whole 2048 x 1024 bf16 expert matrix
_GMM_RHS_BLOCK_BYTES = 4 << 20
#: rows a step multiplies (the tile of ``lhs`` and of the result)
_GMM_ROWS = 128


def select_gmm_tiling(m: int, k: int, n: int, itemsize: int = 2) -> tuple[int, int, int]:
    """``(tm, tk, tn)`` for a call's shape. The whole contraction a step
    (no accumulator round trip) and as much of ``n`` as keeps the staged
    block of ``rhs`` within ``_GMM_RHS_BLOCK_BYTES``; ``tm`` rows, fewer
    when the call has fewer (a multiple of 16, bf16's sublane tile)."""
    tm = min(_GMM_ROWS, -(-m // 16) * 16)
    tk = k
    tn = n
    while tk * tn * itemsize > _GMM_RHS_BLOCK_BYTES and tn % 256 == 0:
        tn //= 2
    while tk * tn * itemsize > _GMM_RHS_BLOCK_BYTES and tk % 256 == 0:
        tk //= 2
    return tm, tk, tn


def gmm_kernel_name(m: int, k: int, n: int, tiling: tuple[int, int, int]) -> str:
    tm, tk, tn = tiling
    return f"moe_gmm_m{m}_k{k}_n{n}_t{tm}x{tk}x{tn}"


def gmm_kernel_runs(interpret: bool) -> bool:
    """Whether :func:`grouped_matmul` takes the Pallas kernel here."""
    return (
        (interpret or jax.default_backend() == "tpu")
        and jax.sharding.get_abstract_mesh().empty
    )


def grouped_matmul(
    lhs: jax.Array,           # (m, k), rows sorted by group
    rhs: jax.Array,           # (groups, k, n)
    group_sizes: jax.Array,   # (groups,) int32, sum <= m
    *,
    interpret: bool = False,
) -> jax.Array:
    """``(m, n)`` in ``lhs``'s type, float32 accumulation. Rows past the
    groups' total belong to no group: what they hold in the result is not
    defined (the caller masks them)."""
    m, k = lhs.shape
    n = rhs.shape[2]
    if not gmm_kernel_runs(interpret):
        return jax.lax.ragged_dot(
            lhs, rhs, group_sizes.astype(jnp.int32),
            preferred_element_type=jnp.float32,
        ).astype(lhs.dtype)
    tiling = select_gmm_tiling(m, k, n, rhs.dtype.itemsize)
    tm = tiling[0]
    pad = -m % tm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = _gmm(lhs, rhs, group_sizes.astype(jnp.int32), tiling=tiling, interpret=interpret)
    return out[:m] if pad else out


@functools.partial(jax.jit, static_argnames=("tiling", "interpret"))
def _gmm(lhs, rhs, group_sizes, *, tiling, interpret):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

    m, k = lhs.shape
    groups, _, n = rhs.shape
    tm, tk, tn = tiling
    if m % tm or k % tk or n % tn:
        raise ValueError(f"tiling {tiling} does not divide (m, k, n) = {(m, k, n)}")
    tiles_k = k // tk
    # (offsets (groups + 1,), group of each step, row tile of each step)
    metadata, steps = make_group_metadata(
        group_sizes=group_sizes, m=m, tm=tm, start_group=jnp.int32(0),
        num_nonzero_groups=groups, visit_empty_groups=False,
    )

    def kernel(metadata, lhs_ref, rhs_ref, out_ref, acc_ref):
        offsets, group_ids, tile_ids = metadata
        step, k_i = pl.program_id(1), pl.program_id(2)

        @pl.when(k_i == 0)
        def _zero():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jax.lax.dot(
            lhs_ref[...], rhs_ref[...], preferred_element_type=jnp.float32
        )

        @pl.when(k_i == tiles_k - 1)
        def _store():
            # the rows of this tile that are this group's
            g = group_ids[step]
            row = tile_ids[step] * tm + jax.lax.broadcasted_iota(
                jnp.int32, (tm, tn), 0
            )
            mine = (row >= offsets[g]) & (row < offsets[g + 1])
            out_ref[...] = jnp.where(
                mine, acc_ref[...], out_ref[...].astype(jnp.float32)
            ).astype(out_ref.dtype)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda n_i, s, k_i, md: (md[2][s], k_i)),
                pl.BlockSpec(
                    (None, tk, tn), lambda n_i, s, k_i, md: (md[1][s], k_i, n_i)
                ),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda n_i, s, k_i, md: (md[2][s], n_i)),
            grid=(n // tn, steps, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(tiling, lhs.dtype.itemsize),
        ),
        interpret=interpret,
        name=gmm_kernel_name(m, k, n, tiling),
    )(metadata, lhs, rhs)


def _vmem_limit(tiling, itemsize: int) -> int | None:
    """Blocks of ``lhs``, ``rhs`` and the result, double-buffered, and the
    float32 accumulator; asked for by size where it passes what a kernel
    gets without asking (None leaves the compiler's limit alone)."""
    tm, tk, tn = tiling
    resident = 2 * itemsize * (tm * tk + tk * tn + tm * tn) + 4 * tm * tn
    return None if resident < (8 << 20) else 2 * resident
