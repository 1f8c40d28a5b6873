"""TPU Pallas kernels for the update-exchange codec (COMPRESSION.md).

The codec is the per-round inner loop of the paper's communication-
efficiency claim: every client's delta crosses the simulated wire int8 /
top-k compressed, so encode wall is paid by every client, every round.
XLA's generic lowering of ``_int8_parts_batched`` materializes the chunk
grid, the abs, the scale broadcast, and the rounded intermediate as
separate HLOs; the :func:`int8_quantize` kernel here runs the whole
pad->absmax->scale->round->clip chain as ONE VMEM pass per block
(QSGD-style quantization is exactly the op class where a fused on-chip
pass beats generic lowering — arXiv 1610.02132).

Parity contract (declared in the registry, pinned in
``tests/test_pallas_codec.py``): **bit-identical** to the XLA reference.
The ledger chains digests over the ENCODED payload and the dist dedup ids
hash the same bytes, so a kernel that is "close" would fork the chain.
Two design rules follow:

- the stochastic-rounding uniforms are PRECOMPUTED outside the kernel
  (``jax.random.uniform`` under each leaf's own ``fold_in`` key, exactly
  as the XLA path draws them) and passed in as an input operand — the
  kernel never touches RNG state, so SEEDED_SCOPE determinism and the
  draw stream are untouched by impl selection;
- the top-k kernel reproduces ``lax.top_k``'s tie-breaking exactly
  (equal |values| -> lower index first) via iterative
  first-occurrence-argmax selection, and extracts the kept values with a
  bit-preserving one-hot min (a one-hot SUM would quietly turn a kept
  ``-0.0`` into ``+0.0``).

Block legalization and interpret-mode detection come from the shared
harness (:mod:`bcfl_tpu.ops.registry`): blocks keep the (8, 128) Mosaic
rule by using 128-multiple (or whole-dim) row blocks, and off-TPU the
kernels run in interpret mode so CPU CI executes the exact kernel bodies.
Oversized top-k rows (a single block must hold the whole row) are turned
away BEFORE the call by the static :func:`topk_supported` predicate the
registry consults: that group is served by the XLA reference, and the
resolved impl name says so. Payloads are bit-identical either way.

Kernel playbook: ``/opt/skills/guides/pallas_guide.md``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from bcfl_tpu.ops import registry

#: one [br, N] row block (plus its abs/iota/onehot temporaries) must fit
#: VMEM; rows wider than this are served by the XLA reference top_k.
#: ~6 live [br, N] f32/int32 buffers at br=8: 10 MB / (8*4*6) ≈ 54k lanes
#: (the widest admitted row, 54613, compiles and runs on a v5e — PERF.md
#: "Bring-up").
TOPK_VMEM_BUDGET_BYTES = 10 << 20
_TOPK_LIVE_BUFFERS = 6

# ------------------------------------------------------- int8 chunk quantize


def _int8_quantize_xla(g, u, *, stochastic: bool):
    """Reference: [C, M, chunk] f32 grid (+ uniforms) -> (q int8, scale f32
    [C, M]). The exact op chain of ``codecs._int8_parts`` after the grid
    reshape — the semantic ground truth the kernel must hit bit-for-bit."""
    scale = jnp.max(jnp.abs(g), axis=-1) / 127.0  # [C, M]
    z = g / jnp.maximum(scale, 1e-30)[..., None]
    if stochastic:
        z = jnp.floor(z + u)
    else:
        z = jnp.round(z)
    q = jnp.clip(z, -127.0, 127.0).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def _int8_kernel(*refs, stochastic: bool):
    if stochastic:
        g_ref, u_ref, q_ref, s_ref = refs
    else:
        g_ref, q_ref, s_ref = refs
    g = g_ref[0]  # [bm, chunk]
    # identical op order to the XLA reference: /127 BEFORE the 1e-30 floor
    scale = jnp.max(jnp.abs(g), axis=-1, keepdims=True) / 127.0  # [bm, 1]
    z = g / jnp.maximum(scale, 1e-30)
    if stochastic:
        z = jnp.floor(z + u_ref[0])
    else:
        z = jnp.round(z)
    q_ref[0] = jnp.clip(z, -127.0, 127.0).astype(jnp.int8)
    s_ref[0] = scale.astype(jnp.float32)


def _int8_quantize_pallas(g, u, *, stochastic: bool, block_m: int = 256):
    """One-VMEM-pass chunk quantize. Grid ``(C, M/bm)``; block
    ``(1, bm, chunk)`` — the chunk axis rides whole (== array dim, always
    legal), bm is a 128-multiple (or the whole M), which satisfies every
    tile in play at once: f32 sublanes (8), int8 sublanes (32), and the
    scale block's lane axis. The scale lands as ``[C, M, 1]`` (last dim ==
    array dim — legal; a bare ``(1, bm)`` block on ``[C, M]`` is the exact
    layout PERF.md documents failing on silicon) and is squeezed here."""
    C, M, chunk = g.shape
    (bm,) = registry.legal_block_sizes(((block_m, M, registry.LANES),))
    grid = (C, pl.cdiv(M, bm))
    in_specs = [pl.BlockSpec((1, bm, chunk), lambda c, m: (c, m, 0))]
    operands = [g]
    if stochastic:
        in_specs.append(pl.BlockSpec((1, bm, chunk), lambda c, m: (c, m, 0)))
        operands.append(u)
    q, s = pl.pallas_call(
        functools.partial(_int8_kernel, stochastic=stochastic),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bm, chunk), lambda c, m: (c, m, 0)),
            pl.BlockSpec((1, bm, 1), lambda c, m: (c, m, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((C, M, chunk), jnp.int8),
            jax.ShapeDtypeStruct((C, M, 1), jnp.float32),
        ],
        interpret=registry.interpret_mode(),
    )(*operands)
    return q, s[..., 0]


# -------------------------------------------------------- top-k magnitude


def _topk_select_xla(x, *, k: int):
    """Reference: [R, N] f32 -> (val f32 [R, k], idx int32 [R, k]) by
    |value| — the exact ``codecs._topk_parts_batched`` inner op pair."""
    _, idx = jax.lax.top_k(jnp.abs(x), k)
    val = jnp.take_along_axis(x, idx, axis=1)
    return val, idx.astype(jnp.int32)


def _topk_kernel(x_ref, val_ref, idx_ref, *, k: int, n: int):
    x = x_ref[...]  # [br, N]
    br = x.shape[0]
    a = jnp.abs(x)
    iota_n = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (br, k), 1)

    def body(j, carry):
        a, vals, idxs = carry
        m = jnp.max(a, axis=-1, keepdims=True)  # [br, 1]
        # first occurrence of the max — lax.top_k's tie-break (equal
        # |values| -> lower index first)
        idx = jnp.min(jnp.where(a == m, iota_n, n), axis=-1,
                      keepdims=True)  # [br, 1]
        sel = iota_n == idx
        # bit-preserving one-hot extract: min over {x, +inf} keeps the
        # selected value's sign bit (a masked SUM would emit +0.0 for a
        # kept -0.0 and break bit-identity with take_along_axis)
        v = jnp.min(jnp.where(sel, x, float("inf")), axis=-1, keepdims=True)
        a = jnp.where(sel, -1.0, a)  # |x| >= 0, so -1 is never re-picked
        vals = jnp.where(iota_k == j, v, vals)
        idxs = jnp.where(iota_k == j, idx, idxs)
        return a, vals, idxs

    _, vals, idxs = jax.lax.fori_loop(
        0, k, body, (a, jnp.zeros((br, k), jnp.float32),
                     jnp.zeros((br, k), jnp.int32)))
    val_ref[...] = vals
    idx_ref[...] = idxs


def _topk_block_rows(R: int) -> int:
    return registry.legal_block(8, R, registry.SUBLANES)


def topk_supported(x, *, k: int) -> bool:
    """Static shape predicate (``KernelOp.supports``): does one row block
    of ``x`` [R, N] fit the kernel's VMEM budget?"""
    del k  # the loop count does not change what a block holds
    R, N = x.shape
    need = _topk_block_rows(R) * N * 4 * _TOPK_LIVE_BUFFERS
    return need <= TOPK_VMEM_BUDGET_BYTES


def _topk_select_pallas(x, *, k: int):
    """Row-blocked magnitude top-k: grid ``(R/br,)``, each block holds br
    whole rows (the N axis == array dim, always legal) and runs k rounds
    of first-occurrence argmax selection — O(k*N) VPU work with zero HBM
    round-trips per round, vs the full sort ``lax.top_k`` lowers to. Wins
    at adapter widths / small k; the microbench records where it does not."""
    R, N = x.shape
    br = _topk_block_rows(R)
    if not topk_supported(x, k=k):
        raise ValueError(
            f"topk_select row block ({br}x{N}) exceeds the "
            f"{TOPK_VMEM_BUDGET_BYTES >> 20} MB VMEM budget; dispatch "
            f"through registry.select, which consults topk_supported")
    val, idx = pl.pallas_call(
        functools.partial(_topk_kernel, k=k, n=N),
        grid=(pl.cdiv(R, br),),
        in_specs=[pl.BlockSpec((br, N), lambda r: (r, 0))],
        out_specs=[
            pl.BlockSpec((br, k), lambda r: (r, 0)),
            pl.BlockSpec((br, k), lambda r: (r, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, k), jnp.float32),
            jax.ShapeDtypeStruct((R, k), jnp.int32),
        ],
        interpret=registry.interpret_mode(),
    )(x)
    return val, idx


# ------------------------------------------------------------- decode ops


def _int8_dequant_xla(q, scale, *, n: int):
    """(q [C, M, chunk], scale [C, M]) -> [C, n] f32, padding stripped.
    XLA-only: dequant is a cheap broadcast multiply XLA already fuses;
    registered so decode selection goes through the same registry and
    degrades to this reference under every ``impl`` request."""
    y = q.astype(jnp.float32) * scale[..., None]
    return y.reshape(q.shape[0], -1)[:, :n]


def _topk_scatter_xla(val, idx, *, n: int):
    """(val [C, k], idx [C, k]) -> dense [C, n] f32 (scatter-by-index)."""
    C, _ = val.shape
    out = jnp.zeros((C, n), jnp.float32)
    return out.at[jnp.arange(C)[:, None], idx].set(val)


# ------------------------------------------------------------ registration

#: microbench rows (scripts/kernel_bench.py): the shapes the codec is paid
#: at — BERT-base leaf widths (768x768 attention, 768x3072 MLP, 768-wide
#: vectors) and the LoRA rank-2/4/8 adapter widths (768*r per adapter
#: half, COMPRESSION.md "Adapter exchange"). C=8 clients per row.
INT8_BENCH_SHAPES = (
    {"label": "bert-attn-768x768", "C": 8, "N": 589824, "chunk": 256},
    {"label": "bert-mlp-768x3072", "C": 8, "N": 2359296, "chunk": 256},
    {"label": "bert-vec-768", "C": 8, "N": 768, "chunk": 256},
    {"label": "lora-r2-1536", "C": 8, "N": 1536, "chunk": 256},
    {"label": "lora-r4-3072", "C": 8, "N": 3072, "chunk": 256},
    {"label": "lora-r8-6144", "C": 8, "N": 6144, "chunk": 256},
)
TOPK_BENCH_SHAPES = (
    {"label": "bert-attn-768x768", "R": 8, "N": 589824},
    {"label": "bert-vec-768", "R": 96, "N": 768},
    {"label": "lora-r2-1536", "R": 96, "N": 1536},
    {"label": "lora-r4-3072", "R": 96, "N": 3072},
    {"label": "lora-r8-6144", "R": 96, "N": 6144},
)

INT8_QUANTIZE = registry.register_op(registry.KernelOp(
    name="int8_quantize",
    xla=_int8_quantize_xla,
    pallas=_int8_quantize_pallas,
    parity="bit-identical",
    bench_shapes=INT8_BENCH_SHAPES,
))

TOPK_SELECT = registry.register_op(registry.KernelOp(
    name="topk_select",
    xla=_topk_select_xla,
    pallas=_topk_select_pallas,
    parity="bit-identical",
    bench_shapes=TOPK_BENCH_SHAPES,
    supports=topk_supported,
))

INT8_DEQUANT = registry.register_op(registry.KernelOp(
    name="int8_dequant",
    xla=_int8_dequant_xla,
    parity="bit-identical",
))

TOPK_SCATTER = registry.register_op(registry.KernelOp(
    name="topk_scatter",
    xla=_topk_scatter_xla,
    parity="bit-identical",
))
