"""Grouped matrix product: rows sorted by group, one weight matrix a group.

``out[r] = lhs[r] @ rhs[g(r)]`` where the rows of ``lhs`` [M, K] come sorted
by group and ``group_sizes`` [G] says how many rows each group has. Rows past
``sum(group_sizes)`` belong to no group and come out zero: an expert layer
that holds a share of the experts sorts the assignments to absent experts
there, so nothing is computed for them and nothing is dropped
(:mod:`bcfl_tpu.models.experts`).

Two implementations behind one signature, through the kernel registry:

- :func:`grouped_matmul_xla` -- ``lax.ragged_dot_general``: the reference,
  and what serves every backend but a single TPU chip.
- :func:`grouped_matmul_pallas` -- the megablox kernel that ships with JAX
  (``jax.experimental.pallas.ops.tpu.megablox``): tiles over the rows, visits
  only the tiles that hold a group's rows (the tail of absent rows costs no
  matrix-unit time) and reads each group's weights in place.

``transpose_rhs`` contracts with ``rhs`` [G, N, K] instead of [G, K, N]: the
activation-gradient product of the same weights, with no transposed copy of
them. Neither implementation has a batching rule for weights that are NOT
batched (``jax.vmap`` of ``lax.ragged_dot`` raises "ragged_dot vmap over any
dim but 0 - NYI"; a Pallas call with scalar prefetch has none): the expert
block that calls this folds the vmapped clients into the rows itself
(``models.experts.expert_block``), so this op only ever sees one row axis.
"""

from __future__ import annotations

import importlib

import jax.numpy as jnp
from jax import lax

from bcfl_tpu.ops import registry

# (rows, contraction, columns) of a kernel tile, clamped to the problem: a
# first choice that fits the kernel's VMEM at [8192, 4096] x [16, 4096, 2048]
# bfloat16, not a tuned one (PERF.md section 7 has the readings by tile).
TILING = (512, 1024, 1024)


def grouped_matmul_xla(lhs, rhs, group_sizes, transpose_rhs: bool = False):
    dims = lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((1,), (2 if transpose_rhs else 1,)), ((), ())),
        lhs_ragged_dimensions=[0], rhs_group_dimensions=[0])
    return lax.ragged_dot_general(
        lhs, rhs, group_sizes.astype(jnp.int32), dims,
        preferred_element_type=jnp.float32).astype(lhs.dtype)


def grouped_matmul_pallas(lhs, rhs, group_sizes, transpose_rhs: bool = False):
    # the kernel's own module (the package's ``gmm`` attribute is its
    # custom_vjp wrapper, whose backward pass would form the weight gradient)
    megablox = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")

    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm, tk, tn = (min(t, d) for t, d in zip(TILING, (m, k, n)))
    pad = -m % tm
    if pad:  # rows of no group: they come out zero and are cut off again
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = megablox.gmm(lhs, rhs, group_sizes.astype(jnp.int32), lhs.dtype,
                       (tm, tk, tn), transpose_rhs=transpose_rhs,
                       interpret=registry.interpret_mode())
    # the kernel leaves the rows of no group unwritten
    live = jnp.arange(m + pad) < group_sizes.sum()
    return jnp.where(live[:, None], out, 0)[:m]


def pallas_supported(lhs, rhs, group_sizes, transpose_rhs: bool = False) -> bool:
    """The kernel wants lane-sized contraction and output extents."""
    k = lhs.shape[1]
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    return k % registry.LANES == 0 and n % registry.LANES == 0


GROUPED_MATMUL = registry.register_op(registry.KernelOp(
    name="moe_grouped_matmul",
    xla=grouped_matmul_xla,
    pallas=grouped_matmul_pallas,
    parity="allclose:2e-2 (float32 accumulation in another order; "
           "pinned in tests/test_latent_moe.py)",
    bench_shapes=(
        # one chunk of the benchmark cell's folded step (models.experts.CHUNK_SHARE)
        {"label": "latent-moe-chunk-8192x4096x2048-16-experts", "M": 8192,
         "K": 4096, "N": 2048, "G": 16, "live": 3200},
    ),
    supports=pallas_supported,
))


def grouped_matmul(lhs, rhs, group_sizes, transpose_rhs: bool = False,
                   impl: str = "auto"):
    """Dispatch through the kernel registry: the megablox kernel where
    ``auto`` selects Pallas (one visible TPU chip) and the shapes are
    lane-sized, ``lax.ragged_dot_general`` elsewhere; whichever is chosen
    runs or raises."""
    fn, _ = registry.select("moe_grouped_matmul", impl, lhs, rhs, group_sizes,
                            transpose_rhs)
    return fn(lhs, rhs, group_sizes, transpose_rhs)
