"""Gated DeltaNet and gated attention in a pattern, with an expert
feed-forward (the ``qwen3_next`` layer), for federated LoRA fine-tuning,
built to run ONE CHIP'S SHARE of a model whose experts and vocabulary are
spread over several chips.

``x0 = Embed(ids)``; a layer is ``h = x + Mix(N1(x))``, ``y = h + MoE(N2(h))``;
``logits = N_f(x_L) W_head`` with the head untied from the embedding. Every
``N`` is the zero-centred RMSNorm ``x rsqrt(mean(x^2) + eps) (1 + w)``
(:class:`ZeroCentredRMSNorm`, ``w`` starts at 0). No bias anywhere. Two kinds
of layer in the published pattern (``layer_types``: full attention every
``full_attention_interval``-th layer, Gated DeltaNet elsewhere); a cut in
depth keeps the pattern's first ``num_layers``.

- **Gated DeltaNet** (:class:`GatedDeltaNet`): ``[q k v z] = in_proj_qkvz(u)``
  and ``[b a] = in_proj_ba(u)``, both laid out a KEY head at a time (q, k,
  then the value heads that read it: v and z; b, a), as published;
  ``[q k v] = silu(conv([q k v]))``, causal and depthwise, WITHOUT bias;
  ``q = l2norm(q) / sqrt(Dk)``, ``k = l2norm(k)``; ``beta = sigmoid(b)`` and
  ``g = -exp(A_log) softplus(a + dt_bias)`` a value head; the gated delta
  rule (:mod:`bcfl_tpu.ops.gated_delta`, chunked), value head ``j`` reading
  key head ``j // (Hv / Hk)``; ``out_proj(RMSNorm_w(o) silu(z))``, an RMSNorm
  over each head (``w`` starts at 1) BEFORE the gate. A row's padding is at
  its tail, so under a causal convolution and rule no real position reads a
  padded one.
- **Gated attention** (:class:`GatedAttention`): ``[q gate] = q_proj(u)`` a
  head at a time; ``q = N_q(q)``, ``k = N_k(k_proj(u))`` (zero-centred, over
  a head); rotary (rotate-half, ``rope_theta``) on the first
  ``partial_rotary_factor`` of each head's dimensions; causal softmax over
  grouped-query heads at scale ``D^-0.5``; ``o_proj(attn * sigmoid(gate))``.
- **Expert layer**: :class:`bcfl_tpu.models.experts.ExpertLayer` (softmax
  over all experts, top k, renormalised: the published order), its shared
  expert times ``sigmoid(shared_expert_gate(u))``.
- **LoRA on the activations** (``experts.LoRADense``) on ``in_proj_qkvz``,
  ``out_proj``, ``q_proj``, ``k_proj``, ``v_proj``, ``o_proj``, the shared
  expert's three and the head. ``in_proj_ba``, the convolution, ``A_log``,
  ``dt_bias``, the norms, the router, the shared expert's gate and the
  routed experts stay frozen.
- **Rematerialisation** (``remat=True``): a layer keeps its input and the
  values :data:`REMAT_SAVED` names; the backward pass runs no product, no
  kernel and no rule of the forward again.
- **Counters**: the expert layer's three and ``gdn_chunks``, the chunks the
  rule ran (rows x chunks a row, a client step a layer).

Named scopes (inside ``fed.forward``): ``fed.gdn`` (the mixer) with
``fed.gdn.conv``, ``fed.gdn.rule`` and ``fed.gdn.gate_norm`` nested in it,
``fed.gattn``, the expert layer's ``fed.moe.*``, ``fed.lm_head``,
``fed.lora``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from bcfl_tpu.metrics.tracing import scope
from bcfl_tpu.models import experts
from bcfl_tpu.models.experts import ExpertLayer, dense
from bcfl_tpu.models.llama import RMSNorm, causal_bias
from bcfl_tpu.models.ssm_moe import causal_conv, conv_init, dt_bias_init
from bcfl_tpu.ops.attention import dot_product_attention
from bcfl_tpu.ops.flash import RESIDUAL_NAMES, flash_attention
from bcfl_tpu.ops.gated_delta import gated_delta_rule, n_chunks

LINEAR, FULL = "linear_attention", "full_attention"
# Qwen/Qwen3-Next-80B-A3B-Instruct: 48 layers, full attention every fourth
PUBLISHED_LAYER_TYPES = tuple(FULL if (i + 1) % 4 == 0 else LINEAR for i in range(48))

# the kernels that carry an adapter
LORA_TARGETS = ("in_proj_qkvz", "out_proj", "q_proj", "k_proj", "v_proj", "o_proj",
                "gate_proj", "up_proj", "down_proj", "lm_head")

COUNTERS = (*(c for c in experts.COUNTERS if c[1] == "sum"),
            ("gdn_chunks", "sum"),
            *(c for c in experts.COUNTERS if c[1] == "max"))

# What a rematerialised layer KEEPS of its forward pass, by the rule of
# ``latent_moe.REMAT_SAVED``. Bytes a position at the published widths,
# bfloat16 unless said; a Gated DeltaNet layer 45,546 and an attention layer
# 39,466 beside the layer's input (4 KB):
REMAT_SAVED = (
    "lora_xa",  # every adapter's x a, float32 [.., r]: 64 B each, 5 or 7 a layer
    "gdn_in_proj_qkvz",  # in_proj_qkvz's output, before the convolution: 24 KB
    "gdn_in_proj_ba",  # in_proj_ba's output: 128 B
    "gdn_o",  # the rule's output, before the norm and gate: 8 KB
    "attn_q_gate",  # q_proj's output [q | gate] a head, before q_norm: 16 KB
    "attn_k", "attn_v",  # k_proj's output before k_norm, and v_proj's: 2 KB
    *RESIDUAL_NAMES,  # the flash kernel's output and log-sum-exp: 8.1 KB
    "mixer_residual",  # the residual stream after the mixer: 4 KB
    "router_logits", "router_idx",  # float32 [.., E] and int32 [.., k]: 2,088 B
    "shared_gate", "shared_up",  # the shared expert's gate and up products: 2 KB
    "shared_out", "shared_gate_logit",  # its output and its gate's product: 4 KB
)


@dataclasses.dataclass(frozen=True)
class DeltaMoEConfig:
    vocab_size: int = 151936  # the rows of the vocabulary this chip holds
    hidden_size: int = 2048
    num_layers: int = 48
    layer_types: Tuple[str, ...] = PUBLISHED_LAYER_TYPES
    num_heads: int = 16
    num_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    gdn_chunk_size: int = 64
    num_experts: int = 512
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512  # one routed expert's width
    shared_expert_intermediate_size: int = 512
    # the routed experts this chip holds: a tuple of expert indices, or a
    # count n meaning experts 0 .. n-1; None = all of them
    experts_held: Optional[Union[int, Tuple[int, ...]]] = None
    rms_eps: float = 1e-6
    initializer_range: float = 0.02
    num_labels: int = 2  # the engine passes it to every family; unused
    use_flash: bool = True
    flash_min_seq: int = 512
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    remat: bool = False
    # read by experts.ExpertLayer: the shared expert is gated
    shared_expert_gate: bool = True

    def __post_init__(self):
        kinds = self.layer_types[:self.num_layers]
        if len(kinds) != self.num_layers or set(kinds) - {LINEAR, FULL}:
            raise ValueError(
                f"{self.num_layers} layers of a pattern of {len(self.layer_types)} "
                f"kinds {sorted(set(self.layer_types))}: a layer is {LINEAR!r} or "
                f"{FULL!r} and a cut keeps the pattern's first layers")
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError("linear_num_value_heads is no multiple of linear_num_key_heads")
        if self.shared_expert_intermediate_size % self.moe_intermediate_size:
            raise ValueError("the shared expert's width is no multiple of an expert's")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError(f"a rotary part of {self.rotary_dim} of {self.head_dim}")

    @property
    def kinds(self) -> Tuple[str, ...]:
        """The kind of each layer run: the pattern's first ``num_layers``."""
        return self.layer_types[:self.num_layers]

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    # what the expert layer reads (models/experts.py)
    @property
    def n_routed_experts(self) -> int:
        return self.num_experts

    @property
    def n_shared_experts(self) -> int:
        return self.shared_expert_intermediate_size // self.moe_intermediate_size

    @property
    def held(self) -> Tuple[int, ...]:
        return experts.held_experts(self.experts_held, self.num_experts)


def _a_log_init(key, shape, dtype):
    """The published draw: ``A`` uniform in (0, 16), stored as its logarithm."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 0.0, 16.0)).astype(dtype)


class ZeroCentredRMSNorm(nn.Module):
    """``x rsqrt(mean(x^2) + eps) (1 + w)`` in float32, ``w`` starting at 0."""

    eps: float
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        w = self.param("weight", nn.initializers.zeros, (x.shape[-1],), self.param_dtype)
        xf = x.astype(jnp.float32)
        y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + self.eps)
        return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def l2norm(x, eps: float = 1e-6):
    """``x rsqrt(sum x^2 + eps)`` over the last axis, float32."""
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.sum(jnp.square(xf), axis=-1, keepdims=True) + eps)


def partial_rope(x, positions, theta: float, rotary_dim: int):
    """Rotary embedding, rotate-half form, on the first ``rotary_dim`` of the
    last axis of ``x`` [B, H, S, D] at ``positions`` [S]; the rest passes."""
    half = rotary_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32) / rotary_dim))
    angles = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    angles = jnp.concatenate([angles, angles], -1)  # [S, rotary_dim]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    xr = x[..., :rotary_dim].astype(jnp.float32)
    turned = jnp.concatenate([-xr[..., half:], xr[..., :half]], -1)
    return jnp.concatenate([(xr * cos + turned * sin).astype(x.dtype), x[..., rotary_dim:]], -1)


class GatedDeltaNet(nn.Module):
    cfg: DeltaMoEConfig

    @nn.compact
    def __call__(self, u):
        c = self.cfg
        B, S, _ = u.shape
        Hk, H = c.linear_num_key_heads, c.linear_num_value_heads
        Dk, Dv, K = c.linear_key_head_dim, c.linear_value_head_dim, c.linear_conv_kernel_dim
        r = H // Hk
        conv_dim = 2 * Hk * Dk + H * Dv
        qkvz = checkpoint_name(dense(c, Hk * (2 * Dk + 2 * r * Dv), "in_proj_qkvz")(u),
                               "gdn_in_proj_qkvz")
        ba = checkpoint_name(dense(c, 2 * H, "in_proj_ba")(u), "gdn_in_proj_ba")
        # a key head at a time: q, k, then its value heads' v and z
        q, k, v, z = jnp.split(qkvz.reshape(B, S, Hk, 2 * Dk + 2 * r * Dv),
                               (Dk, 2 * Dk, 2 * Dk + r * Dv), axis=-1)
        ba = ba.reshape(B, S, Hk, 2 * r)
        b, a = ba[..., :r].reshape(B, S, H), ba[..., r:].reshape(B, S, H)
        # kernel[k] weighs the position K - 1 - k back; no bias
        kernel = self.param("conv_kernel", conv_init(K), (K, conv_dim), c.param_dtype)
        a_log = self.param("A_log", _a_log_init, (H,), c.param_dtype)
        dt_bias = self.param("dt_bias", dt_bias_init, (H,), c.param_dtype)
        with scope("gdn.conv"):
            mixed = jnp.concatenate([q.reshape(B, S, Hk * Dk), k.reshape(B, S, Hk * Dk),
                                     v.reshape(B, S, H * Dv)], -1)
            mixed = nn.silu(causal_conv(mixed, kernel)).astype(c.dtype)
        q = (l2norm(mixed[..., :Hk * Dk].reshape(B, S, Hk, Dk), c.rms_eps)
             * Dk ** -0.5).astype(c.dtype)
        k = l2norm(mixed[..., Hk * Dk:2 * Hk * Dk].reshape(B, S, Hk, Dk), c.rms_eps).astype(c.dtype)
        v = mixed[..., 2 * Hk * Dk:].reshape(B, S, H, Dv)
        beta = jax.nn.sigmoid(b.astype(jnp.float32))
        g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(
            a.astype(jnp.float32) + dt_bias.astype(jnp.float32))
        with scope("gdn.rule"):
            o = checkpoint_name(gated_delta_rule(q, k, v, g, beta, chunk=c.gdn_chunk_size), "gdn_o")
        self.sow("counters", "gdn_chunks", jnp.float32(B * n_chunks(S, c.gdn_chunk_size)),
                 init_fn=lambda: 0.0, reduce_fn=jnp.add)
        with scope("gdn.gate_norm"):
            o = RMSNorm(c.rms_eps, c.param_dtype, name="norm")(o.astype(jnp.float32))
            gated = (o * nn.silu(z.reshape(B, S, H, Dv).astype(jnp.float32))).astype(c.dtype)
        return dense(c, c.hidden_size, "out_proj")(gated.reshape(B, S, H * Dv))


class GatedAttention(nn.Module):
    cfg: DeltaMoEConfig

    @nn.compact
    def __call__(self, x, bias, key_bias, positions):
        c = self.cfg
        B, S, _ = x.shape
        H, KV, D = c.num_heads, c.num_kv_heads, c.head_dim
        qg = checkpoint_name(dense(c, H * 2 * D, "q_proj")(x), "attn_q_gate").reshape(B, S, H, 2 * D)
        k = checkpoint_name(dense(c, KV * D, "k_proj")(x), "attn_k").reshape(B, S, KV, D)
        v = checkpoint_name(dense(c, KV * D, "v_proj")(x), "attn_v").reshape(B, S, KV, D)
        q = ZeroCentredRMSNorm(c.rms_eps, c.param_dtype, name="q_norm")(qg[..., :D])
        k = ZeroCentredRMSNorm(c.rms_eps, c.param_dtype, name="k_norm")(k)
        q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
        q, k = (partial_rope(a, positions, c.rope_theta, c.rotary_dim) for a in (q, k))
        k, v = (jnp.repeat(a, H // KV, axis=1) for a in (k, v))
        # the ops scale by D^-0.5, the published scale
        if bias is None:
            out = flash_attention(q, k, v, key_bias, causal=True)
        else:
            out = dot_product_attention(q, k, v, bias)
        out = out.transpose(0, 2, 1, 3)
        out = (out.astype(jnp.float32) * jax.nn.sigmoid(qg[..., D:].astype(jnp.float32))).astype(c.dtype)
        return dense(c, c.hidden_size, "o_proj")(out.reshape(B, S, H * D))


class DeltaMoELayer(nn.Module):
    cfg: DeltaMoEConfig
    kind: str  # LINEAR | FULL

    @nn.compact
    def __call__(self, x, bias, key_bias, positions):
        c = self.cfg
        h = ZeroCentredRMSNorm(c.rms_eps, c.param_dtype, name="input_norm")(x)
        if self.kind == LINEAR:
            with scope("gdn"):
                mixed = GatedDeltaNet(c, name="linear_attn")(h)
        else:
            with scope("gattn"):
                mixed = GatedAttention(c, name="self_attn")(h, bias, key_bias, positions)
        x = checkpoint_name(x + mixed, "mixer_residual")
        h = ZeroCentredRMSNorm(c.rms_eps, c.param_dtype, name="post_attention_norm")(x)
        return x + ExpertLayer(c, name="moe")(h, key_bias > -1.0)


class DeltaMoELM(nn.Module):
    """The decoder with its LM head: ``apply(vars, ids, mask) -> [B, S, V]``
    float32 logits over the vocabulary rows held."""

    cfg: DeltaMoEConfig
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
        layer_cls = DeltaMoELayer
        if c.remat:
            layer_cls = nn.remat(DeltaMoELayer, policy=(
                jax.checkpoint_policies.save_only_these_names(*REMAT_SAVED)))
        for i, kind in enumerate(c.kinds):
            x = layer_cls(c, kind, name=f"layer_{i}")(x, bias, key_bias, positions)
        x = ZeroCentredRMSNorm(c.rms_eps, c.param_dtype, name="final_norm")(x)
        with scope("lm_head"):
            return dense(c, c.vocab_size, "lm_head", out_dtype=jnp.float32)(x)
