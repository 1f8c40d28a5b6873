import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bcfl_tpu.ops.attention import attention_bias_from_mask, dot_product_attention
from bcfl_tpu.ops.flash import flash_attention_xla


def test_flash_matches_dense_attention():
    rng = np.random.default_rng(0)
    B, H, S, D = 2, 4, 256, 16
    q = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    mask = np.ones((B, S), np.int32)
    mask[1, 200:] = 0
    bias = attention_bias_from_mask(jnp.asarray(mask))

    dense = dot_product_attention(q, k, v, bias)
    flash = flash_attention_xla(q, k, v, bias, block_size=64)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(dense), atol=2e-5)


def test_flash_long_sequence_under_jit():
    B, H, S, D = 1, 2, 2048, 8
    q = jnp.ones((B, H, S, D), jnp.bfloat16)
    out = jax.jit(lambda a: flash_attention_xla(a, a, a, None, block_size=256))(q)
    assert out.shape == (B, H, S, D) and out.dtype == jnp.bfloat16
    assert np.isfinite(np.asarray(out, np.float32)).all()


def test_model_use_flash_path_runs():
    from bcfl_tpu.models import build

    model = build("tiny-bert", use_flash=True, max_position=1024)
    ids = jnp.ones((1, 512), jnp.int32)
    mask = jnp.ones((1, 512), jnp.int32)
    params = model.init(jax.random.key(0), ids, mask)
    logits = model.apply(params, ids, mask)
    assert logits.shape == (1, 2)


def test_causal_flash_matches_dense_causal():
    from bcfl_tpu.models.llama import causal_bias

    rng = np.random.default_rng(1)
    B, H, S, D = 2, 2, 128, 8
    q, k, v = (jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
               for _ in range(3))
    mask = np.ones((B, S), np.int32)
    mask[0, 100:] = 0
    dense = dot_product_attention(q, k, v, causal_bias(jnp.asarray(mask)))
    key_bias = jnp.asarray((1 - mask) * -1e30, jnp.float32)[:, None, None, :]
    flash = flash_attention_xla(q, k, v, key_bias, block_size=32, causal=True)
    # padded/fully-masked rows differ (dense: uniform over nothing vs flash 0);
    # compare only live query positions
    live = np.asarray(mask, bool)
    for b in range(B):
        np.testing.assert_allclose(np.asarray(flash)[b, :, live[b]],
                                   np.asarray(dense)[b, :, live[b]], atol=2e-5)


def test_causal_flash_gradients():
    rng = np.random.default_rng(2)
    B, H, S, D = 1, 2, 64, 8
    q, k, v = (jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
               for _ in range(3))

    from bcfl_tpu.models.llama import causal_bias

    bias = causal_bias(jnp.ones((B, S), jnp.int32))

    gf = jax.grad(lambda q, k, v: flash_attention_xla(
        q, k, v, None, block_size=16, causal=True).sum(), (0, 1, 2))(q, k, v)
    gd = jax.grad(lambda q, k, v: dot_product_attention(
        q, k, v, bias).sum(), (0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)


def test_causal_flash_suffix_query_alignment():
    # Sq != Sk (decode pattern): query at local 0 = global position Sk - Sq
    rng = np.random.default_rng(4)
    B, H, S, D = 1, 2, 64, 8
    q, k, v = (jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
               for _ in range(3))
    full = flash_attention_xla(q, k, v, None, block_size=16, causal=True)
    tail = flash_attention_xla(q[:, :, -8:], k, v, None, block_size=16,
                               causal=True)
    np.testing.assert_allclose(np.asarray(tail), np.asarray(full[:, :, -8:]),
                               atol=2e-5)


def _all_avals(jaxpr):
    """Every intermediate aval in a jaxpr, recursing into sub-jaxprs."""
    out = []
    for eqn in jaxpr.eqns:
        out.extend(v.aval for v in eqn.outvars)
        for p in eqn.params.values():
            if hasattr(p, "jaxpr"):  # ClosedJaxpr
                out.extend(_all_avals(p.jaxpr))
            elif isinstance(p, (list, tuple)):
                out.extend(a for x in p if hasattr(x, "jaxpr")
                           for a in _all_avals(x.jaxpr))
    return out


def test_key_bias_path_never_materializes_dense_scores():
    """A key-side bias ([B,1,1,Sk]) must ride the O(S) path: no intermediate
    of the full [B,H,S,Sk] score size may exist in the program (regression:
    the bias used to be broadcast dense)."""
    B, H, S, D = 2, 4, 256, 16
    q = jnp.ones((B, H, S, D), jnp.float32)
    key_bias = jnp.zeros((B, 1, 1, S), jnp.float32)

    jaxpr = jax.make_jaxpr(
        lambda q, b: flash_attention_xla(q, q, q, b, block_size=64))(q, key_bias)
    dense_size = B * H * S * S
    big = [a for a in _all_avals(jaxpr.jaxpr)
           if hasattr(a, "shape") and np.prod(a.shape, dtype=int) >= dense_size]
    assert not big, f"dense-scores-sized intermediates found: {big}"


def test_dense_bias_fallback_matches_dense_attention():
    """An arbitrary per-(head, query) bias still works via the documented
    dense fallback and matches plain attention."""
    rng = np.random.default_rng(7)
    B, H, S, D = 2, 2, 128, 8
    q, k, v = (jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
               for _ in range(3))
    bias = jnp.asarray(rng.normal(size=(B, H, S, S)), jnp.float32)
    dense = dot_product_attention(q, k, v, bias)
    flash = flash_attention_xla(q, k, v, bias, block_size=32)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(dense), atol=3e-5)


def test_dispatcher_selects_before_the_call_and_never_falls_back(monkeypatch):
    """Where ``auto`` means Pallas (one TPU chip), the kernel is chosen from shapes alone, before
    the call: a dense per-(head, query) bias goes to the XLA path without
    the kernel ever being entered, and a kernel that fails after being
    chosen is an error — no call-time switch to the reference."""
    from bcfl_tpu.ops import flash as flash_mod, registry

    monkeypatch.setattr(registry, "pallas_by_default", lambda: True)

    def boom(*a, **kw):
        raise ValueError("mosaic refused the kernel")

    monkeypatch.setattr(flash_mod, "flash_attention_pallas", boom)
    q = jnp.ones((1, 2, 64, 8), jnp.float32)
    for key_bias in (None, jnp.zeros((1, 64)), jnp.zeros((1, 1, 1, 64))):
        with pytest.raises(ValueError, match="mosaic refused"):
            flash_mod.flash_attention(q, q, q, key_bias)
    dense_bias = jnp.zeros((1, 2, 64, 64), jnp.float32)
    out = flash_mod.flash_attention(q, q, q, dense_bias)
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(flash_mod.flash_attention_xla(q, q, q, dense_bias)))
