"""Llama-family decoder (RMSNorm / RoPE / SwiGLU / GQA) for federated LoRA
fine-tuning.

The reference never runs a decoder LLM — its models are encoder classifiers
(SURVEY.md §2.1) — but the BASELINE.json north-star configs include
"Llama-2-7B LoRA federated fine-tune, 64 clients on v5e-64" (configs[4]).
This module provides that model family TPU-first:

- bf16 compute / f32 params, static shapes, additive causal+padding bias,
- classification head pools the LAST non-pad token (decoder convention,
  mirroring HF ``LlamaForSequenceClassification``) so the same federated
  client step / loss (:mod:`bcfl_tpu.fed.client_step`) trains it unchanged,
- an LM head for causal-LM local objectives,
- tensor-parallel PartitionSpecs via :func:`tp_specs` — attention heads and
  MLP hidden dim sharded over a ``tp`` mesh axis (the scaling-book megatron
  layout: column-parallel in, row-parallel out),
- LoRA targets (:data:`LORA_TARGETS`) for :mod:`bcfl_tpu.models.lora`.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import flax.linen as nn
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from bcfl_tpu.ops.attention import dot_product_attention

# lm_head is a LoRA target (not a full-trained head): on llama2-7b it is
# ~131M params, so full training would defeat the adapter-only
# communication win; the small classifier head full-trains via
# bcfl_tpu.models.lora.HEAD_MODULES
LORA_TARGETS = ("q_proj", "k_proj", "v_proj", "o_proj",
                "gate_proj", "up_proj", "down_proj", "lm_head")


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: Optional[int] = None  # None = MHA; < num_heads = GQA
    intermediate_size: int = 11008
    max_position: int = 4096
    num_labels: int = 2
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    use_flash: bool = True  # blockwise causal attention (no dense [S,S] bias)
    flash_min_seq: int = 512  # below this, dense attention is faster
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    # sequence parallelism hook: a callable (q, k, v, key_bias, causal=...)
    # -> out that replaces the attention op — e.g. ring attention over a
    # 'seq' mesh axis (bcfl_tpu.parallel.sp.ring_config). Static module
    # config; None = the flash/dense selection above.
    attention_override: Optional[Callable] = None
    # per-layer activation rematerialization (nn.remat): O(num_layers) less
    # activation HBM for ~1/3 more FLOPs (see EncoderConfig.remat)
    remat: bool = False

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads


class RMSNorm(nn.Module):
    eps: float
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones,
                           (x.shape[-1],), self.param_dtype)
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
        y = x.astype(jnp.float32) * jnp.reciprocal(jnp.sqrt(var + self.eps))
        return (y * scale.astype(jnp.float32)).astype(x.dtype)


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
         freqs: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Rotary embedding over [B, H, S, D] with positions [B, S] or [S],
    rotating the pairs (2i, 2i + 1). ``freqs`` [D / 2] replaces the plain
    ``theta^(-2i/D)`` (YaRN: models.latent_moe.yarn_inv_freq)."""
    D = x.shape[-1]
    if freqs is None:
        freqs = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[:, :, None].astype(jnp.float32) * freqs[None, None, :]
    cos = jnp.cos(angles)[:, None, :, :]  # [B, 1, S, D/2]
    sin = jnp.sin(angles)[:, None, :, :]
    x1, x2 = x[..., ::2].astype(jnp.float32), x[..., 1::2].astype(jnp.float32)
    r1 = x1 * cos - x2 * sin
    r2 = x1 * sin + x2 * cos
    out = jnp.stack([r1, r2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


class LlamaAttention(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, bias, key_bias, positions):
        """``bias`` is the dense [B,1,S,S] path (None when flash is active);
        ``key_bias`` [B,S] is the padding mask for the flash path."""
        c = self.cfg
        dense = lambda name, heads: nn.DenseGeneral(  # noqa: E731
            features=(heads, c.head_dim), use_bias=False,
            dtype=c.dtype, param_dtype=c.param_dtype, name=name)
        q = dense("q_proj", c.num_heads)(x).transpose(0, 2, 1, 3)
        k = dense("k_proj", c.kv_heads)(x).transpose(0, 2, 1, 3)
        v = dense("v_proj", c.kv_heads)(x).transpose(0, 2, 1, 3)
        q = rope(q, positions, c.rope_theta)
        k = rope(k, positions, c.rope_theta)
        if c.kv_heads != c.num_heads:  # GQA: repeat KV groups
            rep = c.num_heads // c.kv_heads
            k = jnp.repeat(k, rep, axis=1)
            v = jnp.repeat(v, rep, axis=1)
        if c.attention_override is not None:
            out = c.attention_override(q, k, v, key_bias, causal=True)
        elif bias is None:
            from bcfl_tpu.ops.flash import flash_attention

            out = flash_attention(q, k, v, key_bias, causal=True)
        else:
            out = dot_product_attention(q, k, v, bias)
        out = out.transpose(0, 2, 1, 3)
        return nn.DenseGeneral(
            features=c.hidden_size, axis=(-2, -1), use_bias=False,
            dtype=c.dtype, param_dtype=c.param_dtype, name="o_proj")(out)


class LlamaMLP(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        d = lambda f, name: nn.Dense(  # noqa: E731
            f, use_bias=False, dtype=c.dtype, param_dtype=c.param_dtype,
            name=name)
        return d(c.hidden_size, "down_proj")(
            nn.silu(d(c.intermediate_size, "gate_proj")(x))
            * d(c.intermediate_size, "up_proj")(x))


class LlamaLayer(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, bias, key_bias, positions):
        c = self.cfg
        h = RMSNorm(c.rms_eps, c.param_dtype, name="input_norm")(x)
        x = x + LlamaAttention(c, name="attention")(h, bias, key_bias, positions)
        h = RMSNorm(c.rms_eps, c.param_dtype, name="post_attention_norm")(x)
        return x + LlamaMLP(c, name="mlp")(h)


def causal_bias(mask: jnp.ndarray, dtype=jnp.float32) -> jnp.ndarray:
    """Causal + key-padding additive bias [B, 1, S, S] from mask [B, S]."""
    S = mask.shape[-1]
    causal = jnp.tril(jnp.ones((S, S), bool))
    ok = causal[None, :, :] & (mask[:, None, :] > 0)
    return jnp.where(ok, 0.0, -1e30).astype(dtype)[:, None, :, :]


class LlamaModel(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, ids, mask, deterministic: bool = True):
        c = self.cfg
        x = nn.Embed(c.vocab_size, c.hidden_size, param_dtype=c.param_dtype,
                     name="embed")(ids).astype(c.dtype)
        use_flash = c.use_flash and ids.shape[1] >= c.flash_min_seq
        # flash/ring paths: causal triangle + padding handled blockwise; the
        # dense [B,1,S,S] bias (O(S^2) memory) only exists for short
        # sequences where it is cheaper than the blockwise recurrence
        bias = (None if use_flash or c.attention_override is not None
                else causal_bias(mask))
        key_bias = jnp.where(mask > 0, 0.0, -1e30).astype(jnp.float32)
        positions = jnp.arange(ids.shape[1])
        # no static args: every LlamaLayer input is an array (or None bias)
        layer_cls = nn.remat(LlamaLayer) if c.remat else LlamaLayer
        for i in range(c.num_layers):
            x = layer_cls(c, name=f"layer_{i}")(x, bias, key_bias, positions)
        return RMSNorm(c.rms_eps, c.param_dtype, name="final_norm")(x)


class LlamaClassifier(nn.Module):
    """Decoder + last-non-pad-token classification head. Same forward
    signature as :class:`bcfl_tpu.models.bert.TextClassifier`, so the
    federated client step is model-agnostic."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(self, ids, mask, type_ids=None, deterministic: bool = True):
        c = self.cfg
        x = LlamaModel(c, name="model")(ids, mask, deterministic)
        last = jnp.maximum(mask.sum(axis=-1) - 1, 0)  # index of last real token
        pooled = jnp.take_along_axis(x, last[:, None, None].astype(jnp.int32), 1)[:, 0]
        return nn.Dense(c.num_labels, use_bias=False, dtype=jnp.float32,
                        param_dtype=c.param_dtype, name="classifier")(pooled)


class LlamaLM(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, ids, mask, deterministic: bool = True):
        c = self.cfg
        x = LlamaModel(c, name="model")(ids, mask, deterministic)
        return nn.Dense(c.vocab_size, use_bias=False, dtype=jnp.float32,
                        param_dtype=c.param_dtype, name="lm_head")(x)


def tp_specs(params, axis: str = "tp"):
    """PartitionSpecs for megatron-style tensor parallelism over ``axis``:
    column-parallel Q/K/V/gate/up (shard output heads/features), row-parallel
    o_proj/down (shard input), everything else replicated. Compose with the
    ``clients`` axis for clients x tp meshes (a client spanning several
    chips)."""
    import jax

    COL = {"q_proj", "k_proj", "v_proj", "gate_proj", "up_proj"}
    ROW = {"o_proj", "down_proj"}

    def spec(path, leaf):
        names = tuple(getattr(p, "key", str(p)) for p in path)
        mod = names[-2] if len(names) >= 2 else ""
        if mod in COL:
            # q/k/v kernel [in, heads, dim] -> shard heads;
            # gate/up kernel [in, out] -> shard out
            return P(None, axis) if leaf.ndim == 2 else P(None, axis, None)
        if mod in ROW:
            # o_proj kernel [heads, dim, out] -> shard heads (input side);
            # down kernel [in, out] -> shard in
            return P(axis, None) if leaf.ndim == 2 else P(axis, None, None)
        return P()

    return jax.tree_util.tree_map_with_path(spec, params)
