"""Latent-attention decoder with an expert feed-forward (the DeepSeek-V3 layer)
for federated LoRA fine-tuning, built to run ONE CHIP'S SHARE of a model
whose experts and vocabulary are spread over several chips.

``h = x + Attn(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``, no biases, untied
head, a final RMSNorm.

- **Latent attention** (MLA): queries and keys/values go through low-rank
  latents (``q_a_proj`` -> RMSNorm -> ``q_b_proj``; ``kv_a_proj`` -> RMSNorm
  -> ``kv_b_proj``); a head's query and key are a no-position part and a
  rotary part, the rotary key is ONE head shared by all; frequencies by YaRN
  (:func:`yarn_inv_freq`), softmax scale :func:`softmax_scale`. Query-key
  and value heads are equally wide here, so the repo's dense and flash
  attention take them as they are; they scale by ``D^-0.5`` alone, so the
  rest of the scale multiplies ``q``.
- **Expert layer**: a float32 softmax router over ALL ``n_routed_experts``,
  the top ``num_experts_per_tok`` renormalised, plus shared experts. The
  layer is told which experts it HOLDS (``experts_held``) and computes their
  part of the result; what the absent experts would add is left out, and
  that partial result goes on (no code stands in for the absent chips or
  their exchange). No token is dropped and no capacity is set: the
  assignments are sorted by expert, the held ones go through one grouped
  product a projection (:mod:`bcfl_tpu.ops.grouped_matmul`), the absent ones
  sort to the tail where nothing is computed. No balance term: the router is
  frozen.
- **LoRA on the activations**: every dense product is a :class:`LoRADense`,
  ``x W + (x a) b`` when the ``lora`` collection carries ``a`` and ``b`` for
  it (``models.policy``): no ``[in, out]`` product ``a b``, no merged kernel
  a client, no weight-gradient product of a frozen kernel.
- **Clients fold into rows**: under the round program's ``vmap`` over
  clients with the frozen base NOT batched, the expert block's own batching
  rule (:func:`expert_block`) runs one grouped product over all clients'
  rows; the base is never broadcast.
- **Rematerialisation** (``remat=True``): ``nn.remat`` a layer with a
  policy that keeps the values :data:`REMAT_SAVED` names, so the backward
  pass recomputes norms, rotary, gate and copies, and no product or kernel.
- **Counters** (collection ``counters``, :data:`COUNTERS`): the real
  positions' assignments that fell on held and on absent experts, and the
  fullest held expert's rows. Padded positions are routed to no expert.

Named scopes (``metrics.tracing.scope``, inside ``fed.forward``): ``fed.mla``,
``fed.moe.route``, ``fed.moe.experts``, ``fed.moe.shared``, ``fed.lm_head``,
``fed.lora``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.custom_batching import custom_vmap

from bcfl_tpu.metrics.tracing import scope
from bcfl_tpu.models.llama import RMSNorm, causal_bias, rope
from bcfl_tpu.ops.attention import dot_product_attention
from bcfl_tpu.ops.flash import RESIDUAL_NAMES, flash_attention
from bcfl_tpu.ops.grouped_matmul import grouped_matmul

# the kernels that carry an adapter; the router and the routed experts stay
# frozen and untargeted
LORA_TARGETS = ("q_a_proj", "q_b_proj", "kv_a_proj", "kv_b_proj", "o_proj",
                "gate_proj", "up_proj", "down_proj", "lm_head")

# what the model counts in a step, and how a count folds over steps, clients
# and rounds: ``(name, "sum" | "max")``, sums first. Per client: the slots
# are one client's assignments, the fullest expert's rows one client's rows.
COUNTERS = (("moe_slots_held", "sum"), ("moe_slots_absent", "sum"),
            ("moe_rows_max", "max"))

# What a rematerialised layer KEEPS of its forward pass (``remat=True``: the
# backward pass of a layer recomputes everything else from these and the
# layer's input). The base is frozen, so a frozen product's backward needs
# its weights and nothing of the forward; what the backward does read are the
# INPUTS of the norms, the gate and the softmax, the adapters' thin products
# and the flash kernels' operands. Kept are the values from which all of
# that follows by elementwise work, a norm or a copy, so no product and no
# kernel of the forward pass runs a second time. A name sits on the value
# the consumers read: an operation whose derivative reads its own input or
# output (a norm, silu, softmax) is recomputed from the nearest kept value
# above it, so the kept value is the product's output, not the norm's.
# Bytes a position a layer at the published widths, bfloat16 unless said
# (53,008 in all; ``FedEngine.remat_saved`` reads a job's own figure off its
# step):
REMAT_SAVED = (
    "lora_xa",  # every adapter's x a, float32 [.., r]: 8 x 64 B
    "mla_q_a",  # q_a_proj's output, before q_a_norm: 2 KB
    "mla_kv_a",  # kv_a_proj's output (latent before kv_a_norm, rotary key): 640 B
    "mla_q", "mla_k", "mla_v",  # as the attention op receives them: 3 x 8 KB
    *RESIDUAL_NAMES,  # the flash kernel's output and log-sum-exp: 8.1 KB
    "mla_residual",  # the residual stream after attention: 8 KB
    "router_logits", "router_idx",  # float32 [.., E] and int32 [.., k]: 528 B
    "shared_gate", "shared_up",  # the shared expert's gate and up products: 8 KB
)


@dataclasses.dataclass(frozen=True)
class LatentMoEConfig:
    vocab_size: int = 131072  # the rows of the vocabulary this chip holds
    hidden_size: int = 4096
    num_layers: int = 36
    num_heads: int = 32
    q_lora_rank: int = 1024
    kv_lora_rank: int = 256
    qk_nope_head_dim: int = 64
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 128
    num_experts_per_tok: int = 4
    n_shared_experts: int = 1
    moe_intermediate_size: int = 2048
    # the routed experts this chip holds: a tuple of expert indices, or a
    # count n meaning experts 0 .. n-1 (the first of n_routed_experts / n
    # equal shares); None = all of them
    experts_held: Optional[Union[int, Tuple[int, ...]]] = None
    rope_theta: float = 10000.0
    rope_factor: float = 128.0
    rope_original_max_position: int = 8192
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    # queries scaled by 1 + beta ln(1 + floor(pos / original max position))
    llama_4_scaling_beta: float = 0.1
    rms_eps: float = 1e-6
    initializer_range: float = 0.02
    num_labels: int = 2  # the engine passes it to every family; unused
    use_flash: bool = True
    flash_min_seq: int = 512
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    # True: a layer keeps its input and the values REMAT_SAVED names for the
    # backward pass and recomputes the rest (norms, rotary, gate, softmax,
    # copies); False: everything is kept
    remat: bool = False

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def held(self) -> Tuple[int, ...]:
        """The held experts' indices, ascending."""
        h = self.experts_held
        if h is None:
            h = self.n_routed_experts
        held = tuple(range(h)) if isinstance(h, int) else tuple(sorted(h))
        if (not held or len(set(held)) != len(held) or held[0] < 0
                or held[-1] >= self.n_routed_experts):
            raise ValueError(
                f"experts_held {self.experts_held!r} is not a set of experts "
                f"out of {self.n_routed_experts}")
        return held


# ------------------------------------------------------- rotary frequencies


def yarn_inv_freq(dim: int, theta: float, factor: float, original_max: int,
                  beta_fast: float, beta_slow: float) -> jnp.ndarray:
    """YaRN's ``dim / 2`` inverse frequencies: interpolated (``/ factor``)
    where a frequency turns fewer than ``beta_slow`` times over the original
    context, unchanged where it turns more than ``beta_fast`` times, a linear
    ramp over the dimensions between."""
    pos = theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)

    def correction_dim(rotations):
        return (dim * math.log(original_max / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return (1.0 / (factor * pos)) * ramp + (1.0 / pos) * (1.0 - ramp)


def softmax_scale(cfg: LatentMoEConfig) -> float:
    """``qk_head_dim^-0.5 * mscale^2``, ``mscale = 0.1 mscale_all_dim
    ln(factor) + 1`` (the rotary cos/sin factor, mscale over the same with
    ``mscale_all_dim``, is 1 when the two are equal, as published)."""
    m = 0.1 * cfg.rope_mscale_all_dim * math.log(cfg.rope_factor) + 1.0
    return cfg.qk_head_dim ** -0.5 * m * m


# ------------------------------------------------------------------- dense


class LoRADense(nn.Module):
    """``x W`` with a 2-D frozen kernel, plus ``(x a) b`` on the activations
    when the ``lora`` collection has this module's ``a`` [in, r] and ``b``
    [r, out]: products in the compute type, adapters stored in their own."""

    features: int
    dtype: jnp.dtype
    param_dtype: jnp.dtype
    init_std: float = 0.02
    out_dtype: Optional[jnp.dtype] = None  # None = the compute type

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.initializers.normal(self.init_std),
                            (x.shape[-1], self.features), self.param_dtype)
        out = self.out_dtype or self.dtype
        x = x.astype(self.dtype)
        y = jnp.dot(x, kernel.astype(self.dtype), preferred_element_type=out)
        if self.has_variable("lora", "a"):
            with scope("lora"):
                a = self.get_variable("lora", "a").astype(self.dtype)
                b = self.get_variable("lora", "b").astype(self.dtype)
                xa = checkpoint_name(
                    jnp.dot(x, a, preferred_element_type=jnp.float32), "lora_xa")
                y = y + jnp.dot(xa.astype(self.dtype), b,
                                preferred_element_type=out)
        return y


def _dense(c: LatentMoEConfig, features: int, name: str, **kw):
    return LoRADense(features, c.dtype, c.param_dtype, c.initializer_range,
                     name=name, **kw)


class SwiGLU(nn.Module):
    cfg: LatentMoEConfig
    width: int

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        gate = checkpoint_name(_dense(c, self.width, "gate_proj")(x), "shared_gate")
        up = checkpoint_name(_dense(c, self.width, "up_proj")(x), "shared_up")
        return _dense(c, c.hidden_size, "down_proj")(nn.silu(gate) * up)


# --------------------------------------------------------------- attention


class LatentAttention(nn.Module):
    cfg: LatentMoEConfig

    @nn.compact
    def __call__(self, x, bias, key_bias, positions):
        c = self.cfg
        B, S, _ = x.shape
        H, dn, dr, dv = (c.num_heads, c.qk_nope_head_dim, c.qk_rope_head_dim,
                         c.v_head_dim)
        cq = RMSNorm(c.rms_eps, c.param_dtype, name="q_a_norm")(
            checkpoint_name(_dense(c, c.q_lora_rank, "q_a_proj")(x), "mla_q_a"))
        q = _dense(c, H * (dn + dr), "q_b_proj")(cq)
        q = q.reshape(B, S, H, dn + dr).transpose(0, 2, 1, 3)
        ckv = checkpoint_name(
            _dense(c, c.kv_lora_rank + dr, "kv_a_proj")(x), "mla_kv_a")
        k_rope = ckv[..., c.kv_lora_rank:][:, None]  # one head [B, 1, S, dr]
        kv = _dense(c, H * (dn + dv), "kv_b_proj")(
            RMSNorm(c.rms_eps, c.param_dtype, name="kv_a_norm")(
                ckv[..., :c.kv_lora_rank]))
        kv = kv.reshape(B, S, H, dn + dv).transpose(0, 2, 1, 3)
        freqs = yarn_inv_freq(dr, c.rope_theta, c.rope_factor,
                              c.rope_original_max_position,
                              c.rope_beta_fast, c.rope_beta_slow)
        q_rope = rope(q[..., dn:], positions, c.rope_theta, freqs=freqs)
        k_rope = rope(k_rope, positions, c.rope_theta, freqs=freqs)
        # the ops scale by D^-0.5 with D = dn + dr: the rest of the softmax
        # scale, and the long-context query scaling, multiply q
        by_pos = 1.0 + c.llama_4_scaling_beta * jnp.log1p(jnp.floor(
            positions.astype(jnp.float32) / c.rope_original_max_position))
        extra = (softmax_scale(c) * math.sqrt(dn + dr)) * by_pos
        q = (jnp.concatenate([q[..., :dn], q_rope], -1)
             * extra[None, None, :, None].astype(q.dtype))
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_rope, (B, H, S, dr))], -1)
        v = kv[..., dn:]
        if dn + dr != dv:
            raise NotImplementedError(
                f"query-key heads of {dn + dr} and value heads of {dv}: the "
                "attention ops take one head size (ROADMAP: attention with "
                "unequal query-key and value heads)")
        q = checkpoint_name(q, "mla_q")
        k = checkpoint_name(k, "mla_k")
        v = checkpoint_name(v, "mla_v")
        if bias is None:
            out = flash_attention(q, k, v, key_bias, causal=True)
        else:
            out = dot_product_attention(q, k, v, bias)
        out = out.transpose(0, 2, 1, 3).reshape(B, S, H * dv)
        return _dense(c, c.hidden_size, "o_proj")(out)


# ------------------------------------------------------------ expert block

# The sorted assignments go through the experts a chunk at a time, and only
# the chunks that hold an assignment to a HELD expert run: those sort first,
# and a chunk is this share of all the assignments, so where a chip holds an
# eighth of the experts one chunk is the rule, and a step whose routing sends
# more to the held experts takes more chunks and drops nothing.
CHUNK_SHARE = 4


def _chunks(slot, cw, G):
    """The N*k assignments sorted by slot (held experts first, in order; the
    absent ones, slot G, at the tail), cut into chunks: ``(rows_of, n, order)``.
    ``rows_of(c)`` gives chunk c's sorted assignments ``idx`` (padded past the
    last), their tokens, which of them fall on a held expert, their combine
    weights (zero elsewhere) and the chunk's group sizes; ``n`` is how many
    chunks hold a held assignment; ``order`` is the sorting permutation."""
    N, k = slot.shape
    M = N * k
    flat = slot.reshape(-1)
    chunk = -(-M // CHUNK_SHARE)
    by_slot = jnp.argsort(flat, stable=True)
    order = jnp.pad(by_slot, (0, CHUNK_SHARE * chunk - M))
    # where each held expert's rows end in the sorted order (no scatter: the
    # TPU compiler merges look-alike scatters into one it cannot emit)
    ends = jnp.searchsorted(flat[by_slot], jnp.arange(G), side="right")
    starts = jnp.concatenate([jnp.zeros((1,), ends.dtype), ends[:-1]])
    total = ends[-1]
    cwf = cw.reshape(-1)

    def rows_of(c):
        lo = c * chunk
        idx = lax.dynamic_slice(order, (lo,), (chunk,))
        live = lo + jnp.arange(chunk) < total
        sizes = (jnp.clip(ends, lo, lo + chunk)
                 - jnp.clip(starts, lo, lo + chunk)).astype(jnp.int32)
        return idx, idx // k, live, jnp.where(live, cwf[idx], 0.0), sizes

    return rows_of, (total + chunk - 1) // chunk, by_slot


def _silu_gate(g, u):
    """``silu(g) * u`` in float32 and what its gradient needs."""
    gf, uf = g.astype(jnp.float32), u.astype(jnp.float32)
    sg = jax.nn.sigmoid(gf)
    return gf, uf, sg


def _expert_block_fwd(x, slot, cw, wg, wu, wd):
    """One row axis: ``x`` [N, H], ``slot`` [N, k] (a held expert's place in
    ``wg``/``wu``/``wd``, or their count G for an absent expert or a padded
    position), ``cw`` [N, k] combine weights. Returns ``(y,)``, ``y``
    [N, H]."""
    rows_of, n, _ = _chunks(slot, cw, wg.shape[0])

    def chunk(c, y):
        with scope("moe.route"):
            _, tok, _, cws, sizes = rows_of(c)
            xs = x[tok]
        with scope("moe.experts"):
            gf, uf, sg = _silu_gate(grouped_matmul(xs, wg, sizes),
                                    grouped_matmul(xs, wu, sizes))
            o = grouped_matmul((gf * sg * uf).astype(x.dtype), wd, sizes)
        with scope("moe.route"):
            return y.at[tok].add(o.astype(jnp.float32) * cws[:, None])

    y = lax.fori_loop(0, n, chunk, jnp.zeros(x.shape, jnp.float32))
    return (y.astype(x.dtype),)


def _expert_block_bwd(x, slot, cw, dy, wg, wu, wd):
    """``(dx, dcw)``: the gate and up products once more, then the
    activation-gradient products of the same frozen weights
    (``transpose_rhs``); no weight-gradient product. The combine weight's
    gradient ``<o, dy>`` is read as ``<silu(g) u, dy W_down^T>``, which the
    pass has anyway."""
    rows_of, n, order = _chunks(slot, cw, wg.shape[0])

    M = slot.size
    span = -(-M // CHUNK_SHARE)

    def chunk(c, carry):
        dx, dcw_sorted = carry
        with scope("moe.route"):
            _, tok, live, cws, sizes = rows_of(c)
            xs, dys = x[tok], dy[tok]
        with scope("moe.experts"):
            gf, uf, sg = _silu_gate(grouped_matmul(xs, wg, sizes),
                                    grouped_matmul(xs, wu, sizes))
            da = grouped_matmul(dys, wd, sizes,
                                transpose_rhs=True).astype(jnp.float32)
            dcws = jnp.where(live, (gf * sg * uf * da).sum(-1), 0.0)
            da = da * cws[:, None]
            dg = (da * uf * sg * (1.0 + gf * (1.0 - sg))).astype(x.dtype)
            du = (da * gf * sg).astype(x.dtype)
            dxs = (grouped_matmul(dg, wg, sizes, transpose_rhs=True).astype(jnp.float32)
                   + grouped_matmul(du, wu, sizes, transpose_rhs=True).astype(jnp.float32))
        with scope("moe.route"):
            # the combine weights' gradient stays in the sorted order here
            return (dx.at[tok].add(dxs),
                    lax.dynamic_update_slice(dcw_sorted, dcws, (c * span,)))

    dx, dcw_sorted = lax.fori_loop(
        0, n, chunk, (jnp.zeros(x.shape, jnp.float32),
                      jnp.zeros((CHUNK_SHARE * span,), jnp.float32)))
    with scope("moe.route"):
        # where each assignment sits in the sorted order: the inverse permutation
        dcw = dcw_sorted[jnp.argsort(order)].reshape(cw.shape)
    return dx.astype(x.dtype), dcw.astype(cw.dtype)


def _fold(fn):
    """``fn`` with the batching rule that folds a ``vmap``'s axis into the
    row axis: the per-row arguments (those before the three weight stacks)
    are reshaped ``[C, N, ...] -> [C * N, ...]``, the weights have to be
    unbatched (a frozen base under a stack of clients), and the results are
    cut back to ``[C, N, ...]``. The sort inside ``fn`` then groups all
    clients' rows by expert, so the grouped products see all the clients'
    rows and each held expert's weights once."""
    wrapped = custom_vmap(fn)

    @wrapped.def_vmap
    def rule(axis_size, in_batched, *args):
        rows, weights = args[:-3], args[-3:]
        if any(jax.tree.leaves(in_batched[-3:])):
            raise NotImplementedError(
                "the expert block under vmap takes expert weights that are "
                "not batched (one frozen base for the stack of clients); "
                "batched expert weights would be held once a client")
        rows = [r if b else jnp.broadcast_to(r[None], (axis_size,) + r.shape)
                for r, b in zip(rows, in_batched[:-3])]
        out = wrapped(*(r.reshape((-1,) + r.shape[2:]) for r in rows), *weights)
        out = tuple(o.reshape((axis_size, -1) + o.shape[1:]) for o in out)
        return out, tuple(True for _ in out)

    return wrapped


_fwd_folded = _fold(_expert_block_fwd)
_bwd_folded = _fold(_expert_block_bwd)


@jax.custom_vjp
def expert_block(x, slot, cw, wg, wu, wd):
    """The held routed experts' part of the layer for rows ``x`` [N, H]:
    ``sum_j cw[n, j] SwiGLU_{slot[n, j]}(x[n])`` over a row's assignments
    that fall on held experts. Differentiable in ``x`` and ``cw``; the expert
    weights are frozen (their cotangent is zero: full fine-tuning of this
    family is refused at config time). Under ``jax.vmap`` with unbatched
    weights the clients fold into the rows (:func:`_fold`)."""
    return _fwd_folded(x, slot, cw, wg, wu, wd)[0]


def _eb_fwd(x, slot, cw, wg, wu, wd):
    return expert_block(x, slot, cw, wg, wu, wd), (x, slot, cw, wg, wu, wd)


def _eb_bwd(res, dy):
    x, slot, cw, wg, wu, wd = res
    dx, dcw = _bwd_folded(x, slot, cw, dy, wg, wu, wd)
    return dx, None, dcw, None, None, None


expert_block.defvjp(_eb_fwd, _eb_bwd)


class ExpertLayer(nn.Module):
    cfg: LatentMoEConfig

    @nn.compact
    def __call__(self, x, valid):
        """``valid`` [B, S]: the real positions. A padded position is routed
        to no expert: it feeds no loss and no real position attends to it,
        and every padded position of a row has the same hidden state, so they
        would all fall on the same experts (a fifth of a step's rows on one
        expert, held or not by the seed's draw)."""
        c = self.cfg
        B, S, Hd = x.shape
        E, k, F = c.n_routed_experts, c.num_experts_per_tok, c.moe_intermediate_size
        held = c.held
        G = len(held)
        init = nn.initializers.normal(c.initializer_range)
        w_r = self.param("router", init, (Hd, E), c.param_dtype)
        wg = self.param("experts_gate", init, (G, Hd, F), c.param_dtype)
        wu = self.param("experts_up", init, (G, Hd, F), c.param_dtype)
        wd = self.param("experts_down", init, (G, F, Hd), c.param_dtype)
        rows = x.reshape(B * S, Hd)
        with scope("moe.route"):
            # exact products of the stored values, float32 sums
            logits = checkpoint_name(
                jnp.dot(rows.astype(jnp.float32), w_r.astype(jnp.float32),
                        precision=lax.Precision.HIGHEST), "router_logits")
            probs = jax.nn.softmax(logits, axis=-1)
            idx = checkpoint_name(lax.top_k(probs, k)[1], "router_idx")
            # the chosen probabilities by a mask, the counts by comparison:
            # a scatter here (top_k's gradient, bincount) is one the TPU
            # compiler has failed on inside the round program's loops
            p = (probs[:, None, :] * jax.nn.one_hot(idx, E, dtype=probs.dtype)).sum(-1)
            cw = p / p.sum(-1, keepdims=True)
            place = np.full((E,), G, np.int32)  # an expert's place, G = absent
            place[list(held)] = np.arange(G)
            real = valid.reshape(B * S, 1)
            slot = jnp.where(real, jnp.asarray(place)[idx], G)
            per_expert = (slot.reshape(-1, 1) == jnp.arange(G)).sum(0)
            n_held = per_expert.sum().astype(jnp.float32)
            self.sow("counters", "moe_slots_held", n_held,
                     init_fn=lambda: 0.0, reduce_fn=jnp.add)
            self.sow("counters", "moe_slots_absent",
                     real.sum().astype(jnp.float32) * k - n_held,
                     init_fn=lambda: 0.0, reduce_fn=jnp.add)
            self.sow("counters", "moe_rows_max",
                     per_expert.max().astype(jnp.float32),
                     init_fn=lambda: 0.0, reduce_fn=jnp.maximum)
        # sort, unsort and combine name themselves fed.moe.route inside, the
        # grouped products fed.moe.experts
        y = expert_block(rows, slot, cw, wg.astype(c.dtype),
                         wu.astype(c.dtype), wd.astype(c.dtype))
        with scope("moe.shared"):
            shared = SwiGLU(c, c.n_shared_experts * F, name="shared_experts")(x)
        return shared + y.reshape(B, S, Hd)


# ------------------------------------------------------------------- model


class LatentMoELayer(nn.Module):
    cfg: LatentMoEConfig

    @nn.compact
    def __call__(self, x, bias, key_bias, positions):
        c = self.cfg
        h = RMSNorm(c.rms_eps, c.param_dtype, name="input_norm")(x)
        with scope("mla"):
            x = checkpoint_name(x + LatentAttention(c, name="attention")(
                h, bias, key_bias, positions), "mla_residual")
        h = RMSNorm(c.rms_eps, c.param_dtype, name="post_attention_norm")(x)
        return x + ExpertLayer(c, name="moe")(h, key_bias > -1.0)


class LatentMoELM(nn.Module):
    """The decoder with its LM head: ``apply(vars, ids, mask) -> [B, S, V]``
    float32 logits over the vocabulary rows held."""

    cfg: LatentMoEConfig
    # read by fed.client_step.model_counters and .remat_saved (not
    # dataclass fields)
    COUNTERS = COUNTERS
    REMAT_SAVED = REMAT_SAVED

    @nn.compact
    def __call__(self, ids, mask, deterministic: bool = True):
        c = self.cfg
        x = nn.Embed(c.vocab_size, c.hidden_size, param_dtype=c.param_dtype,
                     embedding_init=nn.initializers.normal(c.initializer_range),
                     name="embed")(ids).astype(c.dtype)
        use_flash = c.use_flash and ids.shape[1] >= c.flash_min_seq
        bias = None if use_flash else causal_bias(mask)
        key_bias = jnp.where(mask > 0, 0.0, -1e30).astype(jnp.float32)
        positions = jnp.arange(ids.shape[1])
        layer_cls = LatentMoELayer
        if c.remat:
            layer_cls = nn.remat(LatentMoELayer, policy=(
                jax.checkpoint_policies.save_only_these_names(*REMAT_SAVED)))
        for i in range(c.num_layers):
            x = layer_cls(c, name=f"layer_{i}")(x, bias, key_bias, positions)
        x = RMSNorm(c.rms_eps, c.param_dtype, name="final_norm")(x)
        with scope("lm_head"):
            return _dense(c, c.vocab_size, "lm_head", out_dtype=jnp.float32)(x)
