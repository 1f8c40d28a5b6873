"""Pallas flash kernels run in interpret mode on the CPU mesh: the exact
kernel bodies (forward online-softmax + hand-written dKV/dQ backward) are
exercised in CI without TPU hardware — forward/gradient parity against the
dense oracle across causal, padded, and uneven-block shapes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bcfl_tpu.ops.attention import attention_bias_from_mask, dot_product_attention
from bcfl_tpu.ops.flash import flash_attention_xla
from bcfl_tpu.ops.pallas_flash import flash_attention as flash_pl


def _qkv(shape, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=shape), jnp.float32)
                 for _ in range(3))


def test_pallas_forward_matches_dense():
    B, H, S, D = 2, 3, 128, 16
    q, k, v = _qkv((B, H, S, D))
    out = flash_pl(q, k, v, None, False, 64, 64)
    ref = dot_product_attention(q, k, v, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_pallas_forward_key_bias_padding():
    B, H, S, D = 2, 2, 128, 8
    q, k, v = _qkv((B, H, S, D), seed=1)
    mask = np.ones((B, S), np.int32)
    mask[0, 100:] = 0
    mask[1, 50:] = 0
    bias4 = attention_bias_from_mask(jnp.asarray(mask))  # [B,1,1,S]
    out = flash_pl(q, k, v, bias4, False, 32, 32)
    ref = dot_product_attention(q, k, v, bias4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_pallas_forward_causal_uneven_blocks():
    # S=96 does not tile into 64-blocks: exercises tail-block masking
    B, H, S, D = 1, 2, 96, 8
    q, k, v = _qkv((B, H, S, D), seed=2)
    out = flash_pl(q, k, v, None, True, 64, 64)
    ref = flash_attention_xla(q, k, v, None, block_size=96, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_pallas_backward_matches_dense():
    B, H, S, D = 1, 2, 128, 8
    q, k, v = _qkv((B, H, S, D), seed=3)

    gp = jax.grad(lambda q, k, v: flash_pl(q, k, v, None, False, 32, 32).sum(),
                  (0, 1, 2))(q, k, v)
    gd = jax.grad(lambda q, k, v: dot_product_attention(q, k, v, None).sum(),
                  (0, 1, 2))(q, k, v)
    for a, b in zip(gp, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)


def test_pallas_backward_causal_and_padded():
    B, H, S, D = 2, 2, 96, 8  # uneven blocks + padding + causal together
    q, k, v = _qkv((B, H, S, D), seed=4)
    mask = np.ones((B, S), np.int32)
    mask[1, 70:] = 0
    key_bias = jnp.asarray((1 - mask) * -1e30, jnp.float32)

    def f_pl(q, k, v):
        return (flash_pl(q, k, v, key_bias, True, 32, 32)
                * jnp.asarray(mask)[:, None, :, None]).sum()

    def f_ref(q, k, v):
        return (flash_attention_xla(q, k, v, key_bias[:, None, None, :],
                                    block_size=32, causal=True)
                * jnp.asarray(mask)[:, None, :, None]).sum()

    gp = jax.grad(f_pl, (0, 1, 2))(q, k, v)
    gd = jax.grad(f_ref, (0, 1, 2))(q, k, v)
    for a, b in zip(gp, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)


def test_pallas_bias_gradient():
    """The hand-written backward produces the key-bias gradient too (the XLA
    oracle differentiates through its dense-bias path)."""
    B, H, S, D = 1, 2, 64, 8
    q, k, v = _qkv((B, H, S, D), seed=5)
    bias = jnp.asarray(np.random.default_rng(6).normal(size=(B, S)) * 0.1,
                       jnp.float32)

    gp = jax.grad(lambda b: flash_pl(q, k, v, b, False, 32, 32).sum())(bias)
    gd = jax.grad(lambda b: flash_attention_xla(
        q, k, v, b[:, None, None, :], block_size=32).sum())(bias)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(gd), atol=3e-5)


def test_pallas_suffix_causal_alignment():
    # Sq != Sk (decode pattern): query at local 0 = global position Sk - Sq
    B, H, S, D = 1, 2, 64, 8
    q, k, v = _qkv((B, H, S, D), seed=7)
    full = flash_pl(q, k, v, None, True, 16, 16)
    tail = flash_pl(q[:, :, -16:], k, v, None, True, 16, 16)
    np.testing.assert_allclose(np.asarray(tail), np.asarray(full[:, :, -16:]),
                               atol=2e-5)


def test_pallas_bf16_under_jit():
    B, H, S, D = 1, 2, 256, 8
    q = jnp.ones((B, H, S, D), jnp.bfloat16)
    out = jax.jit(lambda a: flash_pl(a, a, a, None, False, 128, 128))(q)
    assert out.shape == (B, H, S, D) and out.dtype == jnp.bfloat16
    assert np.isfinite(np.asarray(out, np.float32)).all()


def test_pallas_backward_uneven_blocks():
    """Backward parity when S does not tile into blocks: the padded-tail
    branch (_zero_oob_rows + qrow>=sq dead-masking) feeds the dk/dv/db
    accumulators — a regression there corrupts gradients silently."""
    B, H, S, D = 2, 2, 80, 8  # 80 / 32 -> tail block of 16 rows
    q, k, v = _qkv((B, H, S, D), seed=8)
    bias = jnp.asarray(np.random.default_rng(9).normal(size=(B, S)) * 0.1,
                       jnp.float32)

    gp = jax.grad(lambda q, k, v, b: flash_pl(q, k, v, b, True, 32, 32).sum(),
                  (0, 1, 2, 3))(q, k, v, bias)
    gd = jax.grad(lambda q, k, v, b: flash_attention_xla(
        q, k, v, b[:, None, None, :], block_size=S, causal=True).sum(),
        (0, 1, 2, 3))(q, k, v, bias)
    for a, b in zip(gp, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)


def test_pallas_odd_block_sizes_clamped():
    """Real-TPU Mosaic requires block last-two dims to divide (8, 128) or
    equal the array dims; odd caller block sizes are clamped to the nearest
    legal ones (caught on silicon — a (1, bk) bias block failed lowering at
    every seq length while interpret mode passed)."""
    from bcfl_tpu.ops.pallas_flash import _block_sizes

    assert _block_sizes(200, 200, 512, 512) == (200, 128)  # bq 200 % 8 == 0
    assert _block_sizes(67, 130, 512, 512) == (64, 128)
    assert _block_sizes(256, 256, 96, 96) == (96, 96)  # == dims: legal as-is
    assert _block_sizes(4, 64, 512, 512) == (8, 128)  # floors at one tile
    # sub-tile request on a sub-tile-multiple dim: the whole dim is the
    # nearest legal block (bk=128 > Sk=96 would pad 32 dead lanes)
    assert _block_sizes(64, 64, 96, 96) == (64, 96)
    assert _block_sizes(4, 64, 6, 6) == (6, 6)  # dim smaller than a tile

    B, H, S, D = 2, 2, 96, 16
    q, k, v = _qkv((B, H, S, D))
    out = flash_pl(q, k, v, None, False, 67, 130)  # odd blocks, clamped
    ref = dot_product_attention(q, k, v, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_head_128_seq_2048_causal_key_padding_matches_xla():
    """The latent-attention decoder's shape (models/latent_moe.py: query-key
    and value heads both 128 wide, rows of 2048, causal, padded keys), where
    the registry's other bench shapes stop at heads of 64: forward and
    backward against the XLA blockwise reference."""
    B, H, S, D = 1, 1, 2048, 128
    ks = jax.random.split(jax.random.key(128), 4)
    q, k, v = (jax.random.normal(ks[i], (B, H, S, D), jnp.float32) * 0.5 for i in range(3))
    t = jax.random.normal(ks[3], (B, H, S, D), jnp.float32)
    key_bias = jnp.where(jnp.arange(S)[None, :] < 1500, 0.0, -1e30).astype(jnp.float32)

    def pallas(q, k, v):
        return flash_pl(q, k, v, key_bias, True, 256, 256)

    def xla(q, k, v):
        return flash_attention_xla(q, k, v, key_bias, block_size=512, causal=True)

    np.testing.assert_allclose(pallas(q, k, v), xla(q, k, v), atol=2e-5, rtol=2e-5)
    gp = jax.grad(lambda *a: (pallas(*a) * t).sum(), (0, 1, 2))(q, k, v)
    gx = jax.grad(lambda *a: (xla(*a) * t).sum(), (0, 1, 2))(q, k, v)
    for a, b in zip(gp, gx):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-4)
    # keys past the padding get no gradient
    assert float(jnp.abs(gp[1][:, :, 1500:]).max()) == 0.0
