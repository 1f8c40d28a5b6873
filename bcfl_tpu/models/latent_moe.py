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
- **Expert layer** (:mod:`bcfl_tpu.models.experts`, shared with the other
  family that has one): a float32 softmax router over ALL
  ``n_routed_experts``, the top ``num_experts_per_tok`` renormalised, plus
  shared experts; the layer is told which experts it HOLDS
  (``experts_held``) and computes their part of the result.
- **LoRA on the activations**: every dense product is an
  ``experts.LoRADense``, ``x W + (x a) b``.
- **Rematerialisation** (``remat=True``): ``nn.remat`` a layer with a
  policy that keeps the values :data:`REMAT_SAVED` names, so the backward
  pass recomputes norms, rotary, gate and copies, and no product or kernel.
- **Counters**: the expert layer's (``experts.COUNTERS``).

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
from jax.ad_checkpoint import checkpoint_name

from bcfl_tpu.metrics.tracing import scope
from bcfl_tpu.models.experts import COUNTERS, ExpertLayer, dense, held_experts
from bcfl_tpu.models.llama import RMSNorm, causal_bias, rope
from bcfl_tpu.ops.attention import dot_product_attention
from bcfl_tpu.ops.flash import RESIDUAL_NAMES, flash_attention

# the kernels that carry an adapter; the router and the routed experts stay
# frozen and untargeted
LORA_TARGETS = ("q_a_proj", "q_b_proj", "kv_a_proj", "kv_b_proj", "o_proj",
                "gate_proj", "up_proj", "down_proj", "lm_head")

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
        return held_experts(self.experts_held, self.n_routed_experts)


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
            checkpoint_name(dense(c, c.q_lora_rank, "q_a_proj")(x), "mla_q_a"))
        q = dense(c, H * (dn + dr), "q_b_proj")(cq)
        q = q.reshape(B, S, H, dn + dr).transpose(0, 2, 1, 3)
        ckv = checkpoint_name(
            dense(c, c.kv_lora_rank + dr, "kv_a_proj")(x), "mla_kv_a")
        k_rope = ckv[..., c.kv_lora_rank:][:, None]  # one head [B, 1, S, dr]
        kv = dense(c, H * (dn + dv), "kv_b_proj")(
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
        return dense(c, c.hidden_size, "o_proj")(out)


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
            return dense(c, c.vocab_size, "lm_head", out_dtype=jnp.float32)(x)
