"""Blockwise (flash) attention for long sequences.

Two implementations behind one signature:

- :func:`flash_attention_xla` — pure-JAX blockwise online-softmax over KV
  blocks via ``lax.scan``. O(S) memory in the sequence instead of the O(S^2)
  score matrix; runs on any backend (and is the CPU-mesh test oracle).
- :func:`flash_attention_pallas` — TPU Pallas kernel (see
  ``/opt/skills/guides/pallas_guide.md``), selected where one TPU chip is
  visible (``registry.pallas_by_default``) for the bias forms it takes
  (:func:`pallas_supported`, decided from shapes before the call); the XLA
  version serves everything else. A kernel failure is an error, never a
  silent switch.

Both support ``causal=True`` (decoder masking) computed from block indices —
no dense ``[S, S]`` bias ever exists, which is what lets the Llama decoder
(:mod:`bcfl_tpu.models.llama`) run at long context.

The reference never needed this (it truncates at 512 tokens — SURVEY.md §5
"long-context: absent"), but long-context is first-class here: this is the
building block that scales fine-tuning past the HF tokenizer cap, and ring
attention in :mod:`bcfl_tpu.parallel` composes it across chips.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
from jax import lax

from bcfl_tpu.ops import registry

DEFAULT_BLOCK = 512

#: The names (``jax.ad_checkpoint.checkpoint_name``) the Pallas kernel's
#: forward rule gives the two residuals no caller can reach, its output and
#: its log-sum-exp (``pallas_flash._vjp_fwd``): a ``jax.checkpoint`` policy
#: that saves them (``save_only_these_names``), with q, k and v, does not
#: run the forward kernel again in the backward pass. Identities elsewhere.
RESIDUAL_NAMES = ("flash_out", "flash_lse")


def flash_attention_xla(
    q: jnp.ndarray,  # [B, H, S, D]
    k: jnp.ndarray,
    v: jnp.ndarray,
    bias: Optional[jnp.ndarray] = None,  # broadcastable to [B, H, S, S]
    block_size: int = DEFAULT_BLOCK,
    causal: bool = False,
) -> jnp.ndarray:
    """Online-softmax blockwise attention (Rabe & Staats / FlashAttention
    recurrence), scanning KV blocks so the full score matrix never exists."""
    B, H, S, D = q.shape
    Sk = k.shape[2]
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    nb = max(Sk // block_size, 1)
    bs = Sk // nb
    if Sk % nb:
        # fall back to one block if the length doesn't tile evenly
        nb, bs = 1, Sk

    kb = k.reshape(B, H, nb, bs, D).transpose(2, 0, 1, 3, 4)  # [nb, B, H, bs, D]
    vb = v.reshape(B, H, nb, bs, D).transpose(2, 0, 1, 3, 4)
    # A key-side bias ([B, Sk], or 4-D with singleton head/query dims — what
    # padding masks produce) stays in [B, Sk] form, blocked [nb, B, bs] and
    # broadcast per KV block inside the scan: no [B, H, S, Sk] buffer ever
    # exists, preserving O(S) memory. Only a genuinely dense per-(head, query)
    # bias falls back to full materialization.
    key_side = bias is not None and (
        bias.ndim == 2
        or (bias.ndim == 4 and bias.shape[1] == 1 and bias.shape[2] == 1))
    if bias is None:
        bb = jnp.zeros((nb, 1, 1, 1, bs), jnp.float32)
    elif key_side:
        kb2 = bias if bias.ndim == 2 else bias[:, 0, 0, :]
        kb2 = jnp.broadcast_to(kb2, (B, Sk)).astype(jnp.float32)
        bb = kb2.reshape(B, nb, bs).transpose(1, 0, 2)  # [nb, B, bs]
    else:
        bias = jnp.broadcast_to(bias, (B, H, S, Sk)).astype(jnp.float32)
        bb = bias.reshape(B, H, S, nb, bs).transpose(3, 0, 1, 2, 4)  # [nb, B, H, S, bs]

    qf = q.astype(jnp.float32) * scale
    # causal alignment for Sq != Sk (suffix-decode pattern): query i sits at
    # global position (Sk - S) + i
    qpos = (Sk - S) + jnp.arange(S)[:, None]  # [S, 1]
    kcol = jnp.arange(bs)[None, :]  # [1, bs]

    NEG = -1e30  # large-negative instead of -inf: exp() underflows to 0
    # without creating (-inf) - (-inf) NaN paths for fully-masked rows

    def step(carry, xs):
        acc, m, l = carry  # acc [B,H,S,D] f32; m,l [B,H,S,1]
        kj, vj, bj, j = xs
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kj.astype(jnp.float32))
        # bj is [B, bs] on the key-side path, [B/1, H/1, S/1, bs] on the dense
        s = s + (bj[:, None, None, :] if bj.ndim == 2 else bj)
        if causal:
            kpos = j * bs + kcol  # [S, bs] via broadcast
            s = jnp.where((kpos > qpos)[None, None], NEG, s)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bhqk,bhkd->bhqd", p, vj.astype(jnp.float32))
        return (acc, m_new, l), None

    init = (
        jnp.zeros((B, H, S, D), jnp.float32),
        jnp.full((B, H, S, 1), NEG, jnp.float32),
        jnp.zeros((B, H, S, 1), jnp.float32),
    )
    (acc, m, l), _ = lax.scan(step, init, (kb, vb, bb, jnp.arange(nb)))
    return (acc / jnp.maximum(l, 1e-9)).astype(q.dtype)


def flash_attention_pallas(q, k, v, bias=None, causal: bool = False,
                           block_q: Optional[int] = None,
                           block_k: Optional[int] = None):
    """TPU Pallas flash kernels; implemented in :mod:`bcfl_tpu.ops.pallas_flash`.

    ``block_q, block_k``: named, they go to all three kernels (forward, dKV,
    dQ). None, which is what :func:`flash_attention` below passes for every
    model, leaves each kernel the pair of ``pallas_flash.DEFAULT_BLOCKS``:
    measured on a TPU v5e by ``scripts/kernel_bench.py --ops
    flash_attention --backward --flash-blocks ...`` at this op's three
    ``bench_shapes`` (PERF.md section 7 has the table), one request for
    every row length, clamped to the row by ``registry.legal_block``."""
    from bcfl_tpu.ops.pallas_flash import flash_attention as _pl

    # positional: custom_vjp functions don't accept keyword arguments
    return _pl(q, k, v, bias, causal, block_q, block_k)


def pallas_supported(q, k, v, bias=None, **_) -> bool:
    """Static predicate (``KernelOp.supports``): the Pallas kernel takes a
    key-side bias only — None, ``[B, Sk]`` or ``[B, 1, 1, Sk]``. A dense
    per-(head, query) bias is served by the XLA blockwise path."""
    if bias is None:
        return True
    B, Sk = q.shape[0], k.shape[2]
    return bias.shape in ((B, Sk), (B, 1, 1, Sk))


# registry entry (PERF.md "Custom kernels"): flash is the harness's
# tolerance-parity client — online-softmax reassociation makes the Pallas
# and XLA paths numerically close, not bit-identical (the pin lives in
# tests/test_pallas_kernels.py). The codec ops are the bit-identical ones.
FLASH_ATTENTION = registry.register_op(registry.KernelOp(
    name="flash_attention",
    xla=flash_attention_xla,
    pallas=flash_attention_pallas,
    parity="allclose:2e-2 (online-softmax reassociation; "
           "pinned in tests/test_pallas_kernels.py)",
    bench_shapes=(
        {"label": "bert-base-B4-S512", "B": 4, "H": 12, "S": 512, "D": 64},
        {"label": "llama-decode-B1-S2048", "B": 1, "H": 8, "S": 2048,
         "D": 64, "causal": True},
        # models/latent_moe.py at its published sizes, as the benchmark's
        # cell runs it: 2 clients x 2 rows folded, query-key and value heads
        # both 128 wide, causal, bfloat16 (pinned in
        # tests/test_pallas_kernels.py)
        {"label": "latent-moe-B4-H32-S2048-D128", "B": 4, "H": 32, "S": 2048,
         "D": 128, "causal": True, "dtype": "bfloat16"},
    ),
    supports=pallas_supported,
))


def flash_attention(q, k, v, bias=None, causal: bool = False,
                    block_size: int = DEFAULT_BLOCK):
    """Dispatch through the kernel registry: Pallas where ``auto`` selects
    it (one visible TPU chip) for the bias forms the kernel takes, XLA
    blockwise elsewhere. The choice is made here, from the backend and the
    argument shapes; whichever impl is chosen either runs or raises.

    ``bias`` here is key-side only ([B, Sk] or [B, 1, 1, Sk]) so both paths
    stay O(S) in memory; use :func:`flash_attention_xla` directly for an
    arbitrary dense bias.
    """
    _, impl = registry.select("flash_attention", "auto", q, k, v, bias)
    if impl == "pallas":
        # the module global (not the registry's captured callable), so
        # tests can monkeypatch the kernel under the dispatcher
        return flash_attention_pallas(q, k, v, bias, causal=causal)
    return flash_attention_xla(q, k, v, bias, block_size=block_size,
                               causal=causal)
