"""Plain reference of the BERT / ALBERT sequence classifier: forward pass and
loss in straightforward ``jax.numpy``, float32, no kernels, no batching over
clients. It follows the published post-LayerNorm encoder
(BertForSequenceClassification / AlbertForSequenceClassification) with the
departures that the configuration's file lists under ``reduced``.

It imports nothing of the program. Parameters are a FLAT dict of float32
arrays named as below (``weights.py`` beside this file makes them from the seed):

  emb.word [V, E]  emb.pos [P, E]  emb.type [T, E]  emb.ln.g/.b [E]
  emb.proj.w [E, H] emb.proj.b [H]           (only when E != H: ALBERT)
  L<i>.q.w/.k.w/.v.w [H, H]  .q.b/.k.b/.v.b [H]   L<i>.o.w [H, H]  L<i>.o.b [H]
  L<i>.ln1.g/.b [H]  L<i>.f1.w [H, F] .f1.b [F]  L<i>.f2.w [F, H] .f2.b [H]
  L<i>.ln2.g/.b [H]                  (ALBERT: only L0, applied num_layers times)
  pool.w [H, H] pool.b [H]   cls.w [H, num_labels] cls.b [num_labels]

``precision`` chooses how the matrix multiplications see their operands:
  "f32"  float32 operands at ``highest`` precision: the reference proper;
  "bf16" operands rounded to bfloat16 (what the configuration states);
  "fp8"  operands rounded to an 8-bit float (4 exponent and 3 mantissa
         bits) with a per-tensor scale: the control, the nearest precision
         BELOW the stated one.
Rounding is ``lax.reduce_precision``: a convert to a narrow type and back is
something XLA:TPU may drop (``xla_allow_excess_precision``), and did.
Everything outside the matrix multiplications stays float32 in all three.
A suffix ``+act`` ("bf16+act", "fp8+act") also rounds every activation to
bfloat16 where a bfloat16 pipeline holds one (after each product, LayerNorm,
gelu, tanh, dropout and residual sum): the control "fp8+act" is then the
stated bfloat16 pipeline with its matrix operands one precision lower.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

NEG = -1e9  # additive mask for padded keys


def _split(precision):
    return (precision[:-4], True) if precision.endswith("+act") else (precision, False)


def act(x, precision):
    """``x`` as a bfloat16 pipeline would hold it (straight-through)."""
    if not _split(precision)[1]:
        return x
    return x + lax.stop_gradient(lax.reduce_precision(x, 8, 7) - x)


def _round_operand(x, precision):
    """``x`` as the matrix unit sees it. The rounding is straight-through: the
    backward pass multiplies the rounded operands too, but no cotangent is
    itself rounded (an unscaled fp8 gradient would underflow to nothing,
    which is a broken run and no lower precision)."""
    precision = _split(precision)[0]
    if precision == "f32":
        return x
    if precision == "bf16":
        q = lax.reduce_precision(x, 8, 7)
    elif precision == "fp8":
        # 4 exponent bits, 3 mantissa bits: the largest finite value is 240
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 240.0
        q = lax.reduce_precision(x / s, 4, 3) * s
    else:
        raise ValueError(f"unknown precision {precision!r}")
    return x + lax.stop_gradient(q - x)


def matmul(a, b, precision):
    """``a @ b`` over the last axis of ``a`` and the first of ``b``."""
    a = _round_operand(a, precision)
    b = _round_operand(b, precision)
    return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)


def einsum(spec, a, b, precision):
    a = _round_operand(a, precision)
    b = _round_operand(b, precision)
    return jnp.einsum(spec, a, b, precision=lax.Precision.HIGHEST)


def layer_norm(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * g + b


def gelu(x):
    return 0.5 * x * (1.0 + lax.erf(x / jnp.sqrt(2.0).astype(x.dtype)))


def dropout(x, keep_mask, rate):
    """``keep_mask`` is a boolean array of x's shape, or None (no dropout)."""
    if keep_mask is None or rate == 0.0:
        return x
    return jnp.where(keep_mask, x / (1.0 - rate), 0.0)


def forward(params, sizes, ids, mask, keep=None, precision="f32"):
    """Logits [B, num_labels] for token ids [B, S] and padding mask [B, S].

    ``sizes`` is the configuration's JSON (hidden_size, num_hidden_layers,
    ...). ``keep`` maps a dropout site to its boolean keep-mask (see
    ``dropout_sites``); None runs without dropout."""
    H = sizes["hidden_size"]
    nh = sizes["num_attention_heads"]
    hd = H // nh
    L = sizes["num_hidden_layers"]
    eps = sizes["layer_norm_eps"]
    rate = sizes["dropout"]
    shared = sizes["share_layers"]
    p = params
    B, S = ids.shape

    def k(site):
        return None if keep is None else keep[site]

    x = p["emb.word"][ids] + p["emb.pos"][jnp.arange(S)][None] + p["emb.type"][0][None, None]
    A = lambda t: act(t, precision)  # noqa: E731
    x = A(layer_norm(A(x), p["emb.ln.g"], p["emb.ln.b"], eps))
    x = A(dropout(x, k("emb"), rate))
    if "emb.proj.w" in p:
        x = A(matmul(x, p["emb.proj.w"], precision) + p["emb.proj.b"])
    bias = jnp.where(mask[:, None, None, :] > 0, 0.0, NEG).astype(jnp.float32)

    for i in range(L):
        n = "L0" if shared else f"L{i}"

        def heads(t):
            return t.reshape(B, S, nh, hd).transpose(0, 2, 1, 3)

        q = heads(A(matmul(x, p[n + ".q.w"], precision) + p[n + ".q.b"]))
        kk = heads(A(matmul(x, p[n + ".k.w"], precision) + p[n + ".k.b"]))
        v = heads(A(matmul(x, p[n + ".v.w"], precision) + p[n + ".v.b"]))
        s = A(einsum("bhqd,bhkd->bhqk", q, kk, precision)) / jnp.sqrt(jnp.float32(hd))
        s = s + bias
        s = s - s.max(-1, keepdims=True)
        e = jnp.exp(s)
        pr = e / (e.sum(-1, keepdims=True) + 1e-9)
        a = A(einsum("bhqk,bhkd->bhqd", A(pr), v, precision))
        a = a.transpose(0, 2, 1, 3).reshape(B, S, H)
        a = A(matmul(a, p[n + ".o.w"], precision) + p[n + ".o.b"])
        a = A(dropout(a, k(f"attn{i}"), rate))
        x = A(layer_norm(A(x + a), p[n + ".ln1.g"], p[n + ".ln1.b"], eps))
        h = A(gelu(A(matmul(x, p[n + ".f1.w"], precision) + p[n + ".f1.b"])))
        h = A(matmul(h, p[n + ".f2.w"], precision) + p[n + ".f2.b"])
        h = A(dropout(h, k(f"mlp{i}"), rate))
        x = A(layer_norm(A(x + h), p[n + ".ln2.g"], p[n + ".ln2.b"], eps))

    pooled = A(jnp.tanh(A(matmul(x[:, 0], p["pool.w"], precision) + p["pool.b"])))
    pooled = A(dropout(pooled, k("pool"), rate))
    # the classifier's product is float32 in every precision the
    # configuration states (the head computes in float32)
    return jnp.matmul(pooled, p["cls.w"], precision=lax.Precision.HIGHEST) + p["cls.b"]


def dropout_sites(sizes, batch, seq):
    """``{site: shape}`` of every dropout in forward order."""
    H, E = sizes["hidden_size"], sizes["embedding_size"]
    sites = {"emb": (batch, seq, E)}
    for i in range(sizes["num_hidden_layers"]):
        sites[f"attn{i}"] = (batch, seq, H)
        sites[f"mlp{i}"] = (batch, seq, H)
    sites["pool"] = (batch, H)
    return sites


def loss_fn(params, sizes, batch, keep=None, precision="f32"):
    """Masked mean cross-entropy over the batch's real examples, with the
    count of correct answers and of examples."""
    logits = forward(params, sizes, batch["ids"], batch["mask"], keep, precision)
    ex = batch["example_mask"].astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    per_ex = -jnp.take_along_axis(logp, batch["labels"][:, None], axis=-1)[:, 0]
    n = jnp.maximum(ex.sum(), 1.0)
    loss = (per_ex * ex).sum() / n
    correct = ((jnp.argmax(logits, -1) == batch["labels"]) * ex).sum()
    return loss, (correct, ex.sum())
