"""State-space and attention hybrid decoder with an expert feed-forward (the
``granitemoehybrid`` layer) for federated LoRA fine-tuning, built to run ONE
CHIP'S SHARE of a model whose experts and vocabulary are spread over several
chips.

``x0 = embedding_multiplier * Embed(ids)``; a layer is ``h = x + r Mix(RMSNorm(x))``,
``y = h + r (MoE(n) + Shared(n))`` with ``n = RMSNorm(h)`` and ``r`` the
``residual_multiplier``; ``logits = (RMSNorm(x_L) E^T) / logits_scaling`` with
``E`` the embedding (tied). No bias anywhere but the convolution's. Two kinds
of layer in the published pattern (``layer_types``); a cut in depth keeps the
pattern's first ``num_layers``.

- **Mamba-2 mixer** (:class:`MambaMixer`): ``[z | xBC | dt] = in_proj(u)``;
  ``xBC = silu(conv1d(xBC))``, depthwise, causal, with bias; ``[x | B | C] =
  xBC``; ``dt = softplus(dt + dt_bias)`` and ``A = -exp(A_log)`` a head; the
  selective scan (:mod:`bcfl_tpu.ops.ssm_scan`, chunked); ``out_proj(
  RMSNorm(y * silu(z)))``, the gate BEFORE the norm, the norm over all of
  ``d_inner``. One group: every head reads the same B and C. A row's padding
  is at its tail, so under a causal convolution and recurrence no real
  position reads a padded one.
- **Attention without positions** (:class:`NoPEAttention`): grouped-query,
  no rotary and no position term, causal, softmax scale
  ``attention_multiplier``. The ops scale by ``D^-0.5``, so the rest
  multiplies ``q``.
- **Expert layer**: :class:`bcfl_tpu.models.experts.ExpertLayer`, the one
  the latent-attention family runs, here with a shared MLP whose gate and up
  projections are one product (:class:`FusedSwiGLU`: ``input_linear``,
  ``output_linear``). The published order is top-k of the logits, then
  their softmax; the layer's softmax over all experts, top-k, renormalised
  is the same up to rounding.
- **LoRA on the activations** (``experts.LoRADense``) on ``in_proj``,
  ``out_proj``, ``q_proj``, ``k_proj``, ``v_proj``, ``o_proj``, the shared
  MLP's two and the head: there the adapter stands beside ``x E^T``
  (:class:`TiedHead`) and the embedding lookup carries none. Router, routed
  experts, convolution and the recurrence's own parameters stay frozen.
- **Rematerialisation** (``remat=True``): a layer keeps its input and the
  values :data:`REMAT_SAVED` names; the backward pass runs no product, no
  kernel and no scan of the forward again.
- **Counters**: the expert layer's three and ``ssm_scan_chunks``, the chunks
  the scan ran (rows x chunks a row, a client step a layer).

Named scopes (inside ``fed.forward``): ``fed.ssm`` (the mixer) with
``fed.ssm.conv``, ``fed.ssm.scan`` and ``fed.ssm.gate_norm`` nested in it,
``fed.attn``, the expert layer's ``fed.moe.*``, ``fed.lm_head``,
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
from bcfl_tpu.models import experts
from bcfl_tpu.models.experts import ExpertLayer, adapted, dense
from bcfl_tpu.models.llama import RMSNorm, causal_bias
from bcfl_tpu.ops.attention import dot_product_attention
from bcfl_tpu.ops.flash import RESIDUAL_NAMES, flash_attention
from bcfl_tpu.ops.ssm_scan import n_chunks, ssm_scan

# ibm-granite/granite-4.0-h-small: 36 mamba and 4 attention layers, one
# attention layer a period of ten, at 5, 15, 25, 35
PUBLISHED_LAYER_TYPES = tuple(
    "attention" if i % 10 == 5 else "mamba" for i in range(40))

# the kernels that carry an adapter (the head's stands beside the tied
# embedding's product: ``models.lora_policy``)
LORA_TARGETS = ("in_proj", "out_proj", "q_proj", "k_proj", "v_proj", "o_proj",
                "input_linear", "output_linear")

COUNTERS = (*(c for c in experts.COUNTERS if c[1] == "sum"),
            ("ssm_scan_chunks", "sum"),
            *(c for c in experts.COUNTERS if c[1] == "max"))

# What a rematerialised layer KEEPS of its forward pass, by the rule of
# ``latent_moe.REMAT_SAVED``: the values from which everything the backward
# pass reads follows by elementwise work, a norm or a copy. Bytes a position
# at the published widths, bfloat16 unless said; a mamba layer 64,704 and an
# attention layer 35,264 beside the layer's input (8 KB):
REMAT_SAVED = (
    "lora_xa",  # every adapter's x a, float32 [.., r]: 64 B each, 4 or 6 a layer
    "ssm_in_proj",  # in_proj's output [z | xBC | dt], before the convolution: 33,536 B
    "ssm_y",  # the scan's output, before the gate: 16 KB
    "attn_q", "attn_k", "attn_v",  # as projected, k and v before their heads repeat: 12 KB
    *RESIDUAL_NAMES,  # the flash kernel's output and log-sum-exp: 8.1 KB
    "mixer_residual",  # the residual stream after the mixer: 8 KB
    "router_logits", "router_idx",  # float32 [.., E] and int32 [.., k]: 328 B
    "shared_in",  # the shared MLP's input_linear product [gate | up]: 6 KB
)


@dataclasses.dataclass(frozen=True)
class SSMMoEConfig:
    vocab_size: int = 100352  # the rows of the vocabulary this chip holds
    hidden_size: int = 4096
    num_layers: int = 40
    layer_types: Tuple[str, ...] = PUBLISHED_LAYER_TYPES
    num_heads: int = 32
    num_kv_heads: int = 8
    attention_multiplier: float = 0.0078125  # the softmax scale
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 16.0
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 256
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    num_local_experts: int = 72
    num_experts_per_tok: int = 10
    intermediate_size: int = 768  # one routed expert's width
    shared_intermediate_size: int = 1536
    # the routed experts this chip holds: a tuple of expert indices, or a
    # count n meaning experts 0 .. n-1; None = all of them
    experts_held: Optional[Union[int, Tuple[int, ...]]] = None
    rms_eps: float = 1e-5
    initializer_range: float = 0.02
    num_labels: int = 2  # the engine passes it to every family; unused
    use_flash: bool = True
    flash_min_seq: int = 512
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    remat: bool = False

    def __post_init__(self):
        kinds = self.layer_types[:self.num_layers]
        if len(kinds) != self.num_layers or set(kinds) - {"mamba", "attention"}:
            raise ValueError(
                f"{self.num_layers} layers of a pattern of {len(self.layer_types)} "
                f"kinds {sorted(set(self.layer_types))}: a layer is 'mamba' or "
                "'attention' and a cut keeps the pattern's first layers")
        if self.mamba_n_groups != 1 or self.mamba_proj_bias or not self.mamba_conv_bias:
            raise NotImplementedError(
                "the mixer runs one group of B and C, projections without bias "
                "and a convolution with one (the published settings)")
        if self.mamba_expand * self.hidden_size != self.mamba_n_heads * self.mamba_d_head:
            raise ValueError("mamba_expand * hidden_size is not mamba_n_heads * mamba_d_head")
        if self.shared_intermediate_size % self.intermediate_size:
            raise ValueError("the shared MLP's width is no multiple of an expert's")

    @property
    def kinds(self) -> Tuple[str, ...]:
        """The kind of each layer run: the pattern's first ``num_layers``."""
        return self.layer_types[:self.num_layers]

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    # what the expert layer reads (models/experts.py)
    @property
    def n_routed_experts(self) -> int:
        return self.num_local_experts

    @property
    def moe_intermediate_size(self) -> int:
        return self.intermediate_size

    @property
    def n_shared_experts(self) -> int:
        return self.shared_intermediate_size // self.intermediate_size

    @property
    def held(self) -> Tuple[int, ...]:
        return experts.held_experts(self.experts_held, self.num_local_experts)


def _a_log_init(key, shape, dtype):
    """Mamba-2's draw: ``A`` uniform in [1, 16], stored as its logarithm."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)).astype(dtype)


def _dt_bias_init(key, shape, dtype):
    """Mamba-2's draw: ``dt`` log-uniform in [0.001, 0.1], stored as the
    value whose softplus it is."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _conv_init(d_conv):
    """torch's Conv1d default, which Mamba-2 leaves: uniform in
    +-1/sqrt(d_conv) for a depthwise kernel [d_conv, channels] and its bias."""
    bound = d_conv ** -0.5

    def init(key, shape, dtype):
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound).astype(dtype)

    return init


class FusedSwiGLU(nn.Module):
    """``output_linear(silu(g) * u)``, ``[g | u] = input_linear(x)``."""

    cfg: SSMMoEConfig
    width: int

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        gu = checkpoint_name(dense(c, 2 * self.width, "input_linear")(x), "shared_in")
        return dense(c, c.hidden_size, "output_linear")(
            nn.silu(gu[..., :self.width]) * gu[..., self.width:])


class MambaMixer(nn.Module):
    cfg: SSMMoEConfig

    @nn.compact
    def __call__(self, u):
        c = self.cfg
        B, S, _ = u.shape
        H, P, N, K = c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state, c.mamba_d_conv
        d_in, conv_dim = c.d_inner, c.d_inner + 2 * N
        zxbcdt = checkpoint_name(
            dense(c, d_in + conv_dim + H, "in_proj")(u), "ssm_in_proj")
        z, xbc, dt = jnp.split(zxbcdt, (d_in, d_in + conv_dim), axis=-1)
        # kernel[k] weighs the position K - 1 - k back
        kernel = self.param("conv_kernel", _conv_init(K), (K, conv_dim), c.param_dtype)
        bias = self.param("conv_bias", _conv_init(K), (conv_dim,), c.param_dtype)
        a_log = self.param("A_log", _a_log_init, (H,), c.param_dtype)
        d_skip = self.param("D", nn.initializers.ones, (H,), c.param_dtype)
        dt_bias = self.param("dt_bias", _dt_bias_init, (H,), c.param_dtype)
        with scope("ssm.conv"):
            padded = jnp.pad(xbc.astype(jnp.float32), ((0, 0), (K - 1, 0), (0, 0)))
            kf = kernel.astype(jnp.float32)
            acc = bias.astype(jnp.float32) + sum(
                kf[k] * padded[:, k:k + S] for k in range(K))
            xbc = nn.silu(acc).astype(c.dtype)
        x = xbc[..., :d_in].reshape(B, S, H, P)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias.astype(jnp.float32))
        with scope("ssm.scan"):
            y = checkpoint_name(ssm_scan(
                x, dt, -jnp.exp(a_log.astype(jnp.float32)), xbc[..., d_in:d_in + N],
                xbc[..., d_in + N:], d_skip.astype(jnp.float32),
                chunk=c.mamba_chunk_size), "ssm_y")
        self.sow("counters", "ssm_scan_chunks",
                 jnp.float32(B * n_chunks(S, c.mamba_chunk_size)),
                 init_fn=lambda: 0.0, reduce_fn=jnp.add)
        with scope("ssm.gate_norm"):
            gated = (y.reshape(B, S, d_in).astype(jnp.float32)
                     * nn.silu(z.astype(jnp.float32)))
            gated = RMSNorm(c.rms_eps, c.param_dtype, name="norm")(gated)
        return dense(c, c.hidden_size, "out_proj")(gated)


class NoPEAttention(nn.Module):
    cfg: SSMMoEConfig

    @nn.compact
    def __call__(self, x, bias, key_bias):
        c = self.cfg
        B, S, _ = x.shape
        H, KV, D = c.num_heads, c.num_kv_heads, c.head_dim

        def heads(name, n):
            return dense(c, n * D, name)(x).reshape(B, S, n, D).transpose(0, 2, 1, 3)

        # the ops scale by D^-0.5: the rest of the softmax scale multiplies q
        q = heads("q_proj", H) * jnp.asarray(
            c.attention_multiplier * math.sqrt(D), c.dtype)
        q = checkpoint_name(q, "attn_q")
        k = checkpoint_name(heads("k_proj", KV), "attn_k")
        v = checkpoint_name(heads("v_proj", KV), "attn_v")
        k, v = (jnp.repeat(a, H // KV, axis=1) for a in (k, v))
        if bias is None:
            out = flash_attention(q, k, v, key_bias, causal=True)
        else:
            out = dot_product_attention(q, k, v, bias)
        out = out.transpose(0, 2, 1, 3).reshape(B, S, H * D)
        return dense(c, c.hidden_size, "o_proj")(out)


class SSMMoELayer(nn.Module):
    cfg: SSMMoEConfig
    kind: str  # "mamba" | "attention"

    @nn.compact
    def __call__(self, x, bias, key_bias):
        c = self.cfg
        r = jnp.asarray(c.residual_multiplier, c.dtype)
        h = RMSNorm(c.rms_eps, c.param_dtype, name="input_norm")(x)
        if self.kind == "mamba":
            with scope("ssm"):
                mixed = MambaMixer(c, name="mamba")(h)
        else:
            with scope("attn"):
                mixed = NoPEAttention(c, name="attention")(h, bias, key_bias)
        x = checkpoint_name(x + r * mixed, "mixer_residual")
        h = RMSNorm(c.rms_eps, c.param_dtype, name="post_attention_norm")(x)
        return x + r * ExpertLayer(c, shared=FusedSwiGLU, name="moe")(h, key_bias > -1.0)


class TiedHead(nn.Module):
    """``(x E^T + (x a) b) / logits_scaling``: the head whose kernel is the
    embedding, with its own adapter beside the product."""

    cfg: SSMMoEConfig

    @nn.compact
    def __call__(self, x, embedding):
        c = self.cfg
        x = x.astype(c.dtype)
        y = jnp.einsum("...h,vh->...v", x, embedding.astype(c.dtype),
                       preferred_element_type=jnp.float32)
        return adapted(self, x, y, c.dtype, jnp.float32) / c.logits_scaling


class SSMMoELM(nn.Module):
    """The decoder with its LM head: ``apply(vars, ids, mask) -> [B, S, V]``
    float32 logits over the vocabulary rows held."""

    cfg: SSMMoEConfig
    # read by fed.client_step.model_counters and .remat_saved (not
    # dataclass fields)
    COUNTERS = COUNTERS
    REMAT_SAVED = REMAT_SAVED

    @nn.compact
    def __call__(self, ids, mask, deterministic: bool = True):
        c = self.cfg
        embed = nn.Embed(c.vocab_size, c.hidden_size, param_dtype=c.param_dtype,
                         embedding_init=nn.initializers.normal(c.initializer_range),
                         name="embed")
        x = (embed(ids).astype(jnp.float32) * c.embedding_multiplier).astype(c.dtype)
        use_flash = c.use_flash and ids.shape[1] >= c.flash_min_seq
        bias = None if use_flash else causal_bias(mask)
        key_bias = jnp.where(mask > 0, 0.0, -1e30).astype(jnp.float32)
        layer_cls = SSMMoELayer
        if c.remat:
            layer_cls = nn.remat(SSMMoELayer, policy=(
                jax.checkpoint_policies.save_only_these_names(*REMAT_SAVED)))
        for i, kind in enumerate(c.kinds):
            x = layer_cls(c, kind, name=f"layer_{i}")(x, bias, key_bias)
        x = RMSNorm(c.rms_eps, c.param_dtype, name="final_norm")(x)
        with scope("lm_head"):
            return TiedHead(c, name="lm_head")(x, embed.embedding)
