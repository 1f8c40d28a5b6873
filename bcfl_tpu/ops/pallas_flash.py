"""TPU Pallas flash-attention kernels — forward AND backward.

Blockwise online-softmax attention (the FlashAttention recurrence) tiled for
the MXU: grid ``(B, H, Sq/bq, Sk/bk)``, with the running max / normalizer /
accumulator living in VMEM scratch that persists across the (innermost) KV
grid dimension. The full ``[S, S]`` score matrix never exists — O(S) memory.
The forward kernel additionally emits the log-sum-exp per query row
(lane-padded ``[B, H, S, 128]``, the layout TPU Mosaic tiles cleanly), which
is what makes a recompute-free backward possible.

Backward = two Pallas kernels (the standard flash backward split):

- ``dKV`` kernel, grid ``(B, H, Sk/bk, Sq/bq)``: for each KV block, rebuild
  the probability block from (q, k, lse), accumulate ``dv += p^T dO``,
  ``dk += ds^T q`` and the key-side bias gradient ``db += sum_q ds`` in VMEM
  scratch over the inner query loop.
- ``dQ`` kernel, grid ``(B, H, Sq/bq, Sk/bk)``: accumulates ``dq += ds k``
  over the inner KV loop.

Both recompute ``s`` from q/k (one extra matmul per block) instead of saving
probabilities — O(S) memory in the backward too. ``D = rowsum(dO * O)`` is
folded into the kernels from the saved output, so no XLA-side pass is needed.

Masking, all computed from block indices (never a dense ``[S, S]`` bias):
- key-side additive bias ``[B, Sk]`` (padding masks, what the encoder's
  :func:`bcfl_tpu.ops.attention.attention_bias_from_mask` produces),
- ``causal=True`` decoder masking with suffix alignment for ``Sq != Sk``
  (query i sits at global position ``Sk - Sq + i`` — the decode pattern),
- out-of-bounds masking of padded tail query rows and key columns when the
  lengths don't tile evenly into blocks.

On non-TPU backends every kernel runs in Pallas interpret mode, so CI
exercises the exact kernel bodies on the CPU mesh (SURVEY.md §4's
distributed-without-hardware strategy applied to kernels).

Block sizes. A caller may name ``block_q, block_k``, which then go to all
three kernels. Where nobody does (the models' dispatcher,
:func:`bcfl_tpu.ops.flash.flash_attention`), each kernel takes its pair
from :data:`DEFAULT_BLOCKS`, one request for every row length:
:func:`_block_sizes` clamps it to the row (a row of 512 runs one 512 x 512
block a head in all three kernels; a row of 2560 runs 2048-blocks with a
masked tail). The pairs were measured on a TPU v5e, not reasoned (PR 29;
PERF.md section 7 has the table at the registry's three bench shapes, each
kernel alone by the device's clock): a grid step costs about a third of a
microsecond whatever it computes, and the 256 x 256 blocks this file was
written with are less work than that at 128-wide heads. Repeat the sweep
on the chip with ``python scripts/kernel_bench.py --ops flash_attention
--backward --iters 20 --flash-blocks "256,256;1024,1024;2048,2048;default"``.
:func:`_blocks` reckons the scoped VMEM a request needs from the block
sizes and the head width (:func:`_vmem_bytes`), asks Mosaic for it where
that is over Mosaic's own default, and refuses named blocks that no chip's
VMEM holds.

Kernel playbook: ``/opt/skills/guides/pallas_guide.md`` (grid/BlockSpec,
VMEM scratch, ``@pl.when`` init/finalize pattern, custom-VJP pattern).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bcfl_tpu.ops import registry
from bcfl_tpu.ops.flash import RESIDUAL_NAMES

NEG_INF = -1e30  # large-negative, not -inf: exp underflows to 0 without NaNs
LANES = 128  # TPU lane width: scratch/lse last dim must be 128

#: The (query, key) block each kernel is asked for when the caller names
#: none: what ``ops.flash.flash_attention`` runs in every model. The sweep
#: of PR 29 on a TPU v5e (``scripts/kernel_bench.py``; PERF.md section 7):
#: at [4, 32, 2048, 128] bfloat16 causal, forward / dKV / dQ alone take
#: 7.12 / 7.58 / 4.79 ms at 256 x 256, 2.23 / 3.08 / 2.48 at 1024 x 1024
#: and 1.55 / 2.96 / 2.13 at whole rows of 2048 (the fastest of eleven
#: pairs for each kernel); the dKV kernel reads 2.99 at 512 x 1024, level
#: with its whole-row time there, and 0.232 against 0.287 ms at heads of
#: 64 ([1, 8, 2048, 64]), so it keeps the smaller pair and Mosaic's
#: default VMEM.
DEFAULT_BLOCKS = {"fwd": (2048, 2048), "dkv": (512, 1024), "dq": (2048, 2048)}

#: Mosaic's scoped-VMEM limit when a call sets none, and the most a call may
#: ask for here: three quarters of the 128 MiB a TensorCore of the TPUs this
#: runs on (v5e, v6e) has, the rest left to the compiler's own buffers.
VMEM_DEFAULT_BYTES = 16 << 20
VMEM_MAX_BYTES = 96 << 20


def _interpret() -> bool:
    """Run kernels in interpret mode off-TPU (CPU CI) — same kernel bodies.
    Delegates to the shared harness knob (``BCFL_PALLAS_INTERPRET``,
    :func:`bcfl_tpu.ops.registry.interpret_mode`) so one toggle governs
    every kernel; kept as a name because callers/tests import it here."""
    return registry.interpret_mode()


def _zero_oob_rows(x, start: int, limit: int):
    """Zero rows of a ``[rows, D]`` block whose global index >= limit.

    Out-of-range block reads are padded with unspecified values (NaN in
    interpret mode); a padded row multiplied by a zero probability still
    poisons a dot product (0 * NaN = NaN), so dead rows must be zeroed at
    load, not just masked downstream."""
    idx = start + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.where(idx < limit, x, jnp.zeros_like(x))


def _when_block_is_live(causal: bool, qi, ki, bq: int, bk: int,
                        sq: int, sk: int):
    """Decorator that runs a kernel's per-block work unless the causal
    triangle masks the whole (query block, key block) pair: its first key
    lies past its last query's position (``sk - sq + i`` for query ``i``).
    Such a block adds nothing to any accumulator, so skipping it changes no
    result; at equal lengths it is nearly half of the grid."""
    if not causal:
        return lambda body: body()
    return pl.when(ki * bk <= (sk - sq) + qi * bq + (bq - 1))


# --------------------------------------------------------------------- forward


def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, out_ref, lse_ref,
                acc_ref, m_ref, l_ref,
                *, scale: float, causal: bool, bq: int, bk: int,
                sq: int, sk: int):
    # read outside the blocks below: a program id is no value inside one
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    @_when_block_is_live(causal, qi, ki, bq, bk, sq, sk)
    def _block():
        q = q_ref[0, 0]  # [bq, D]
        k = _zero_oob_rows(k_ref[0, 0], ki * bk, sk)  # [bk, D]
        v = _zero_oob_rows(v_ref[0, 0], ki * bk, sk)  # [bk, D]
        b = bias_ref[0, 0]  # [bk]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [bq, bk]
        s = s + b[None, :].astype(jnp.float32)

        # block-index masking: padded tail keys + (optionally) the causal
        # triangle
        kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        dead = kpos >= sk
        if causal:
            # suffix alignment for Sq != Sk (decode pattern): query i sits at
            # global position (sk - sq) + i — matches flash_attention_xla
            qpos = (sk - sq) + qi * bq + (
                jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0))
            dead = jnp.logical_or(dead, kpos > qpos)
        s = jnp.where(dead, NEG_INF, s)

        m_prev = m_ref[:, :1]  # [bq, 1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(dead, 0.0, p)  # exp(NEG-NEG)=1 on all-masked rows otherwise
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == nk - 1)
    def _finalize():
        out_ref[0, 0] = (
            acc_ref[:] / jnp.maximum(l_ref[:, :1], 1e-9)
        ).astype(out_ref.dtype)
        lse_ref[0, 0] = m_ref[:] + jnp.log(jnp.maximum(l_ref[:], 1e-30))


def _block_sizes(block_q: int, block_k: int, S: int, Sk: int):
    """Clamp requested block sizes to shapes real-TPU Mosaic accepts: the
    last two dims of every block must divide (8, 128) or equal the array
    dims. bq tiles a sublane-adjacent dim (multiple of 8); bk tiles the
    bias lane dim (multiple of 128). A caller's odd block size becomes the
    nearest legal one instead of an obscure lowering error on silicon.
    The rule now lives in the shared harness
    (:func:`bcfl_tpu.ops.registry.legal_block_sizes`); this name stays as
    the flash-specific binding callers/tests import."""
    return registry.legal_block_sizes(
        ((block_q, S, registry.SUBLANES), (block_k, Sk, LANES)))


#: What a kernel holds in VMEM at once, for :func:`_vmem_bytes`: ``[bq, D]``
#: blocks and ``[bk, D]`` blocks (operands and results) and float32
#: ``[bq, bk]`` temporaries: the forward's ``s`` and ``p`` with their mask,
#: the backward's ``s``, ``p``, ``dp`` and ``ds``.
_VMEM_COUNTS = {"fwd": (2, 2, 3), "dkv": (3, 4, 4), "dq": (4, 2, 4)}


def _vmem_bytes(kernel: str, bq: int, bk: int, D: int, dtype) -> int:
    """The scoped VMEM ``kernel`` needs at blocks ``bq x bk``, reckoned
    from what it holds at once (:data:`_VMEM_COUNTS`): every operand and
    result block twice (the pipeline's double buffers; the lane-padded
    log-sum-exp and the bias row in float32), the float32 accumulators and
    the score temporaries. An upper estimate, since the compiler shares
    buffers: compiled for a described v5e at head widths of 64, 128 and 256
    in bfloat16 and float32 (75 combinations), its own count was at most
    0.9 of this one (whole rows of 2048 at 128-wide bfloat16 heads: 41.7,
    50.2 and 41.0 MB for forward, dKV and dQ against 60.9, 80.8 and 79.8
    here)."""
    q_blocks, k_blocks, score_tiles = _VMEM_COUNTS[kernel]
    item = jnp.dtype(dtype).itemsize
    blocks = 2 * ((q_blocks * bq + k_blocks * bk) * D * item
                  + (bq * LANES + bk) * 4)
    scratch = (2 * max(bq, bk) * D + 2 * bq * LANES + 8 * bk) * 4
    return blocks + scratch + score_tiles * bq * bk * 4


def _blocks(kernel: str, block_q: Optional[int], block_k: Optional[int],
            S: int, Sk: int, D: int, dtype):
    """``kernel``'s legal (query, key) block and its Mosaic parameters.

    The caller's blocks, else the kernel's own pair of
    :data:`DEFAULT_BLOCKS`, clamped by :func:`_block_sizes`. A pair nobody
    named gives way to a wide head: its longer side is halved until
    :data:`VMEM_MAX_BYTES` hold it (the measured pairs fit heads of 256 in
    bfloat16 whole). Named blocks that cannot fit raise with their sizes.
    Over Mosaic's default the reckoned bytes are asked for; under it
    nothing is."""
    named = block_q is not None or block_k is not None
    dq, dk = DEFAULT_BLOCKS[kernel]
    bq, bk = _block_sizes(dq if block_q is None else block_q,
                          dk if block_k is None else block_k, S, Sk)
    need = _vmem_bytes(kernel, bq, bk, D, dtype)
    while need > VMEM_MAX_BYTES and not named and max(bq, bk) > LANES:
        bq, bk = _block_sizes(*((bq // 2, bk) if bq >= bk else (bq, bk // 2)),
                              S, Sk)
        need = _vmem_bytes(kernel, bq, bk, D, dtype)
    if need > VMEM_MAX_BYTES:
        tiles = _VMEM_COUNTS[kernel][2]
        raise ValueError(
            f"flash {kernel} kernel: blocks of {bq} x {bk} at a head width "
            f"of {D} ({jnp.dtype(dtype).name}) need about {need >> 20} MiB "
            f"of VMEM ({tiles} float32 score tiles of "
            f"{(bq * bk * 4) >> 20} MiB each); at most "
            f"{VMEM_MAX_BYTES >> 20} MiB can be asked for: name smaller "
            "blocks")
    params = (None if need <= VMEM_DEFAULT_BYTES
              else pltpu.CompilerParams(vmem_limit_bytes=need))
    return bq, bk, params


def _flash_fwd_pallas(q, k, v, key_bias, causal: bool,
                      block_q: Optional[int], block_k: Optional[int]):
    """Returns ``(out [B,H,S,D], lse [B,H,S,LANES] f32)``."""
    B, H, S, D = q.shape
    Sk = k.shape[2]
    bq, bk, params = _blocks("fwd", block_q, block_k, S, Sk, D, q.dtype)
    grid = (B, H, pl.cdiv(S, bq), pl.cdiv(Sk, bk))
    scale = 1.0 / (D ** 0.5)

    key_bias = key_bias[:, None, :]  # [B, 1, Sk] — see bias BlockSpec note
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, sq=S, sk=Sk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, qi, ki: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, qi, ki: (b, h, ki, 0)),
            # [B, 1, Sk] with block (1, 1, bk): real-TPU Mosaic requires the
            # last two block dims to divide (8, 128) or EQUAL the array dims
            # — a (1, bk) block on [B, Sk] fails that for B > 1 (caught on
            # silicon; interpret mode never checks it)
            pl.BlockSpec((1, 1, bk), lambda b, h, qi, ki: (b, 0, ki)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bq, LANES), lambda b, h, qi, ki: (b, h, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((B, H, S, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),      # acc
            pltpu.VMEM((bq, LANES), jnp.float32),  # running max
            pltpu.VMEM((bq, LANES), jnp.float32),  # running normalizer
        ],
        compiler_params=params,
        interpret=_interpret(),
    )(q, k, v, key_bias)


# -------------------------------------------------------------------- backward


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, do_ref, lse_ref,
                    dk_ref, dv_ref, db_ref, dk_acc, dv_acc, db_acc,
                    *, scale: float, causal: bool, bq: int, bk: int,
                    sq: int, sk: int):
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)
        db_acc[:] = jnp.zeros_like(db_acc)

    @_when_block_is_live(causal, qi, ki, bq, bk, sq, sk)
    def _block():
        q = _zero_oob_rows(q_ref[0, 0], qi * bq, sq)    # [bq, D]
        k = k_ref[0, 0]    # [bk, D]
        v = v_ref[0, 0]    # [bk, D]
        o = _zero_oob_rows(o_ref[0, 0], qi * bq, sq)    # [bq, D]
        do = _zero_oob_rows(do_ref[0, 0], qi * bq, sq)  # [bq, D]
        b = bias_ref[0, 0]  # [bk]
        lse = lse_ref[0, 0][:, :1]  # [bq, 1]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale + b[None, :].astype(jnp.float32)
        kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        qrow = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        # padded tail QUERY rows must be masked here: unlike the forward (where
        # garbage rows land in the discarded output slice) they would otherwise
        # contribute to the dk/dv/db accumulators
        dead = jnp.logical_or(kpos >= sk, qrow >= sq)
        if causal:
            dead = jnp.logical_or(dead, kpos > (sk - sq) + qrow)
        p = jnp.where(dead, 0.0, jnp.exp(s - lse))  # [bq, bk]

        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bk, D]

        dsum = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(
            axis=-1, keepdims=True)  # [bq, 1] = rowsum(dO * O)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bk]
        # explicit re-mask: dp/dsum can carry NaN/Inf from padded tail reads and
        # 0 * NaN = NaN would survive p's zeros
        ds = jnp.where(dead, 0.0, p * (dp - dsum))  # [bq, bk] f32

        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [bk, D]
        db_acc[0:1, :] = db_acc[0:1, :] + ds.sum(axis=0)[None, :]

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)
        db_ref[0, 0] = db_acc[0:1, :].astype(db_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, do_ref, lse_ref,
                   dq_ref, dq_acc,
                   *, scale: float, causal: bool, bq: int, bk: int,
                   sq: int, sk: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @_when_block_is_live(causal, qi, ki, bq, bk, sq, sk)
    def _block():
        q = q_ref[0, 0]
        k = _zero_oob_rows(k_ref[0, 0], ki * bk, sk)
        v = _zero_oob_rows(v_ref[0, 0], ki * bk, sk)
        o = o_ref[0, 0]
        do = do_ref[0, 0]
        b = bias_ref[0, 0]  # [bk]
        lse = lse_ref[0, 0][:, :1]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale + b[None, :].astype(jnp.float32)
        kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        dead = kpos >= sk
        if causal:
            qpos = (sk - sq) + qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            dead = jnp.logical_or(dead, kpos > qpos)
        p = jnp.where(dead, 0.0, jnp.exp(s - lse))

        dsum = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(
            axis=-1, keepdims=True)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = jnp.where(dead, 0.0, p * (dp - dsum))

        dq_acc[:] = dq_acc[:] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [bq, D]

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_pallas(q, k, v, key_bias, out, do, lse, causal: bool,
                          block_q: Optional[int], block_k: Optional[int]):
    """The key side of the backward: ``(dk, dv, db[B, Sk])``."""
    B, H, S, D = q.shape
    Sk = k.shape[2]
    bq, bk, params = _blocks("dkv", block_q, block_k, S, Sk, D, q.dtype)
    nq = pl.cdiv(S, bq)
    nk = pl.cdiv(Sk, bk)
    key_bias = key_bias[:, None, :]  # [B, 1, Sk] — see forward BlockSpec note

    dk, dv, db_h = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=1.0 / (D ** 0.5),
                          causal=causal, bq=bq, bk=bk, sq=S, sk=Sk),
        grid=(B, H, nk, nq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, ki, qi: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, ki, qi: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, ki, qi: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, h, ki, qi: (b, 0, ki)),
            pl.BlockSpec((1, 1, bq, D), lambda b, h, ki, qi: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bq, D), lambda b, h, ki, qi: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bq, LANES), lambda b, h, ki, qi: (b, h, qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, D), lambda b, h, ki, qi: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, ki, qi: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, 1, bk), lambda b, h, ki, qi: (b, h, 0, ki)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct((B, H, 1, Sk), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((8, bk), jnp.float32),  # db row accumulator (8-sublane)
        ],
        compiler_params=params,
        interpret=_interpret(),
    )(q, k, v, key_bias, out, do, lse)
    # [B, Sk]: the bias is shared across heads and queries
    return dk, dv, db_h.sum(axis=(1, 2))


def _flash_bwd_dq_pallas(q, k, v, key_bias, out, do, lse, causal: bool,
                         block_q: Optional[int], block_k: Optional[int]):
    """The query side of the backward: ``dq``."""
    B, H, S, D = q.shape
    Sk = k.shape[2]
    bq, bk, params = _blocks("dq", block_q, block_k, S, Sk, D, q.dtype)
    nq = pl.cdiv(S, bq)
    nk = pl.cdiv(Sk, bk)
    key_bias = key_bias[:, None, :]  # [B, 1, Sk] — see forward BlockSpec note

    return pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=1.0 / (D ** 0.5),
                          causal=causal, bq=bq, bk=bk, sq=S, sk=Sk),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, qi, ki: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, qi, ki: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, h, qi, ki: (b, 0, ki)),
            pl.BlockSpec((1, 1, bq, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bq, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bq, LANES), lambda b, h, qi, ki: (b, h, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=params,
        interpret=_interpret(),
    )(q, k, v, key_bias, out, do, lse)


# ------------------------------------------------------------------ public API


def _normalize_bias(bias, B: int, Sk: int) -> jnp.ndarray:
    """Accept ``[B, Sk]`` / ``[B, 1, 1, Sk]`` / None -> ``[B, Sk]`` f32."""
    if bias is None:
        return jnp.zeros((B, Sk), jnp.float32)
    if bias.ndim == 4:
        if bias.shape[1] != 1 or bias.shape[2] != 1:
            raise ValueError(
                "pallas flash attention supports key-side bias only "
                f"([B,1,1,Sk]); got {bias.shape}")
        bias = bias[:, 0, 0, :]
    if bias.shape != (B, Sk):
        raise ValueError(f"bias shape {bias.shape} != {(B, Sk)}")
    return bias.astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def flash_attention(q, k, v, bias=None, causal: bool = False,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None):
    """[B, H, S, D] x3 (+ key bias [B, Sk]) -> [B, H, S, D].

    ``block_q, block_k``: a named block goes to all three kernels (forward,
    dKV, dQ); None leaves each kernel its own pair of
    :data:`DEFAULT_BLOCKS`, the measured ones. Either way the request is
    clamped to the row length and to Mosaic's (8, 128) rule."""
    key_bias = _normalize_bias(bias, q.shape[0], k.shape[2])
    out, _ = _flash_fwd_pallas(q, k, v, key_bias, causal, block_q, block_k)
    return out


def _vjp_fwd(q, k, v, bias, causal, block_q, block_k):
    key_bias = _normalize_bias(bias, q.shape[0], k.shape[2])
    out, lse = _flash_fwd_pallas(q, k, v, key_bias, causal, block_q, block_k)
    # The kernel writes a row's log-sum-exp into all LANES lanes: one is
    # kept, [B, H, S], and spread again where the backward kernels read it.
    # Both residuals are named for a rematerialising caller's policy
    # (ops.flash.RESIDUAL_NAMES); a name is the identity elsewhere.
    out = checkpoint_name(out, RESIDUAL_NAMES[0])
    lse = checkpoint_name(lse[..., 0], RESIDUAL_NAMES[1])
    return out, (q, k, v, bias, key_bias, out, lse)


def _vjp_bwd(causal, block_q, block_k, res, g):
    q, k, v, bias, key_bias, out, lse = res
    lse = jnp.broadcast_to(lse[..., None], lse.shape + (LANES,))
    args = (q, k, v, key_bias, out, g, lse, causal, block_q, block_k)
    dk, dv, db = _flash_bwd_dkv_pallas(*args)
    dq = _flash_bwd_dq_pallas(*args)
    if bias is None:
        return dq, dk, dv, None
    return dq, dk, dv, db.astype(bias.dtype).reshape(bias.shape)


flash_attention.defvjp(_vjp_fwd, _vjp_bwd)
