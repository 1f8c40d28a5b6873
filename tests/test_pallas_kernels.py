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


@pytest.mark.parametrize("enclosing", ["nothing", "checkpoint", "checkpoint_that_keeps_the_names"])
def test_pallas_residual_names_change_no_value_and_no_gradient(enclosing):
    """The forward rule names the kernel's output and log-sum-exp
    (``ops.flash.RESIDUAL_NAMES``) and keeps one lane of the second: the
    value and the gradients are the dense oracle's, and bit for bit the same
    whether nothing encloses the call, a ``jax.checkpoint`` with no policy
    (the forward kernel runs again in the backward pass) or one whose policy
    keeps the two names (it does not)."""
    from bcfl_tpu.ops.flash import RESIDUAL_NAMES

    B, H, S, D = 2, 2, 96, 8  # uneven blocks + padding + causal together
    q, k, v = _qkv((B, H, S, D), seed=4)
    mask = np.ones((B, S), np.int32)
    mask[1, 70:] = 0
    key_bias = jnp.asarray((1 - mask) * -1e30, jnp.float32)
    real = jnp.asarray(mask)[:, None, :, None]

    def plain(q, k, v):
        return (flash_pl(q, k, v, key_bias, True, 32, 32) * real).sum()

    fn = {"nothing": plain, "checkpoint": jax.checkpoint(plain),
          "checkpoint_that_keeps_the_names": jax.checkpoint(
              plain, policy=jax.checkpoint_policies.save_only_these_names(*RESIDUAL_NAMES))}[enclosing]
    value, grads = jax.value_and_grad(fn, (0, 1, 2))(q, k, v)
    want_value, want = jax.value_and_grad(plain, (0, 1, 2))(q, k, v)
    assert float(value) == float(want_value)
    for a, b in zip(grads, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    oracle = jax.grad(lambda q, k, v: (flash_attention_xla(
        q, k, v, key_bias[:, None, None, :], block_size=32, causal=True) * real).sum(), (0, 1, 2))(q, k, v)
    for a, b in zip(grads, oracle):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)
    calls = str(jax.make_jaxpr(jax.grad(fn, (0, 1, 2)))(q, k, v)).count("pallas_call")
    assert calls == (4 if enclosing == "checkpoint" else 3)
    # one lane of the log-sum-exp is what the backward pass is handed
    _, vjp = jax.vjp(plain, q, k, v)
    assert (B, H, S) in {tuple(x.shape) for x in jax.tree.leaves(vjp)}
    assert (B, H, S, 128) not in {tuple(x.shape) for x in jax.tree.leaves(vjp)}


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


# ---- the blocks the models' dispatcher runs (pallas_flash.DEFAULT_BLOCKS) ----

_DEFAULT_BLOCK_CASES = {
    # the benchmark's expert-decoder cell: the request divides the row
    "cell-2048-d128-causal-padded": dict(shape=(1, 1, 2048, 128), sk=2048, causal=True, keep=1500),
    # a row shorter than the request: one clamped block a head
    "row-512-d64-key-bias": dict(shape=(2, 2, 512, 64), sk=512, causal=False, keep=400, noise=True),
    # a row no power of two divides: whole-row blocks of 1536, or a masked
    # tail after the 1024 keys of a dKV block
    "row-1536-d64-causal": dict(shape=(1, 2, 1536, 64), sk=1536, causal=True, keep=None),
    # a row longer than every request: 2048-blocks with a masked tail of 512
    "row-2560-d64-causal": dict(shape=(1, 1, 2560, 64), sk=2560, causal=True, keep=None),
    # fewer queries than keys: suffix alignment across a key-block boundary
    "sq-256-sk-1280-causal": dict(shape=(1, 2, 256, 64), sk=1280, causal=True, keep=None),
}


@pytest.mark.parametrize("case", list(_DEFAULT_BLOCK_CASES))
def test_default_blocks_match_xla_and_256_blocks(case):
    """The kernels at the dispatcher's default blocks, forward and every
    gradient (dq, dk, dv and the key bias's), against the XLA blockwise
    reference and against the same kernels at the 256 x 256 blocks they ran
    before PR 29: block sizes change the order of float32 sums and nothing
    else."""
    c = _DEFAULT_BLOCK_CASES[case]
    B, H, S, D = c["shape"]
    Sk = c["sk"]
    ks = jax.random.split(jax.random.key(29), 5)
    q = jax.random.normal(ks[0], (B, H, S, D), jnp.float32) * 0.5
    k, v = (jax.random.normal(ks[i], (B, H, Sk, D), jnp.float32) * 0.5 for i in (1, 2))
    t = jax.random.normal(ks[3], (B, H, S, D), jnp.float32)
    bias = jnp.zeros((B, Sk), jnp.float32)
    if c.get("noise"):
        bias = bias + jax.random.normal(ks[4], (B, Sk), jnp.float32)
    if c["keep"] is not None:
        bias = jnp.where(jnp.arange(Sk)[None, :] < c["keep"], bias, -1e30)

    def outs(fn):
        def loss(*a):
            out = fn(*a)
            return (out * t).sum(), out
        grads, out = jax.grad(loss, argnums=(0, 1, 2, 3), has_aux=True)(q, k, v, bias)
        return (out,) + grads

    got = outs(lambda q, k, v, b: flash_pl(q, k, v, b, c["causal"]))
    old = outs(lambda q, k, v, b: flash_pl(q, k, v, b, c["causal"], 256, 256))
    ref = outs(lambda q, k, v, b: flash_attention_xla(
        q, k, v, b, block_size=256, causal=c["causal"]))
    for name, a, b256, x in zip(("out", "dq", "dk", "dv", "dbias"), got, old, ref):
        np.testing.assert_allclose(a, x, atol=5e-5, rtol=5e-4, err_msg=f"{name} vs xla")
        np.testing.assert_allclose(a, b256, atol=5e-5, rtol=5e-4, err_msg=f"{name} vs 256")
    if c["keep"] is not None:  # keys past the padding get no gradient
        assert float(jnp.abs(got[2][:, :, c["keep"]:]).max()) == 0.0


def _pallas_grids(fn, *args):
    """``{kernel name: grid}`` of the Pallas calls in ``fn``'s jaxpr."""
    found = {}

    def walk(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                found[e.params["jaxpr"].debug_info.func_name] = tuple(
                    e.params["grid_mapping"].grid)
            for val in e.params.values():
                for j in (val if isinstance(val, (list, tuple)) else [val]):
                    inner = getattr(j, "jaxpr", j)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def test_dispatcher_hands_each_kernel_its_default_blocks(monkeypatch):
    """``ops.flash.flash_attention`` under ``impl == "pallas"`` names no
    block, so each of the three kernels runs its own pair of
    ``pallas_flash.DEFAULT_BLOCKS`` (read off the jaxpr's grids: three
    distinct pairs here, then the module's own); blocks named to
    ``flash_attention_pallas`` reach all three."""
    from bcfl_tpu.ops import flash as flash_mod, pallas_flash, registry

    monkeypatch.setattr(registry, "pallas_by_default", lambda: True)
    B, H, S, D = 1, 2, 2048, 16
    q = jnp.ones((B, H, S, D), jnp.float32)
    key_bias = jnp.zeros((B, S), jnp.float32)

    def through(attend):
        return _pallas_grids(
            jax.grad(lambda q, k, v: attend(q, k, v, key_bias, causal=True).sum(), (0, 1, 2)),
            q, q, q)

    def grids(blocks):  # dKV's grid runs (key blocks, query blocks)
        (fq, fk), (kq, kk), (dq, dk) = (
            pallas_flash._block_sizes(*blocks[name], S, S) for name in ("fwd", "dkv", "dq"))
        return {"_fwd_kernel": (B, H, -(-S // fq), -(-S // fk)),
                "_bwd_dkv_kernel": (B, H, -(-S // kk), -(-S // kq)),
                "_bwd_dq_kernel": (B, H, -(-S // dq), -(-S // dk))}

    assert through(flash_mod.flash_attention) == grids(pallas_flash.DEFAULT_BLOCKS)
    apart = {"fwd": (512, 256), "dkv": (256, 1024), "dq": (1024, 512)}
    monkeypatch.setattr(pallas_flash, "DEFAULT_BLOCKS", apart)
    assert through(flash_mod.flash_attention) == {
        "_fwd_kernel": (B, H, 4, 8), "_bwd_dkv_kernel": (B, H, 2, 8),
        "_bwd_dq_kernel": (B, H, 2, 4)} == grids(apart)
    named = through(lambda *a, **kw: flash_mod.flash_attention_pallas(
        *a, block_q=128, block_k=512, **kw))
    assert named == {"_fwd_kernel": (B, H, 16, 4), "_bwd_dkv_kernel": (B, H, 4, 16),
                     "_bwd_dq_kernel": (B, H, 16, 4)}


def test_vmem_request_is_reckoned_from_the_blocks_and_refused_past_the_chip():
    """Small blocks ask Mosaic for nothing; score tiles past its default ask
    for what they need; named blocks no chip holds raise with their sizes;
    the default request gives way to a wide head and is never refused."""
    from bcfl_tpu.ops import pallas_flash as pf

    for kernel in ("fwd", "dkv", "dq"):
        assert pf._blocks(kernel, 256, 256, 2048, 2048, 128, jnp.bfloat16) == (256, 256, None)
        bq, bk, big = pf._blocks(kernel, 2048, 1024, 4096, 4096, 128, jnp.bfloat16)
        tiles = pf._VMEM_COUNTS[kernel][2] * 2048 * 1024 * 4
        assert (bq, bk) == (2048, 1024)
        assert tiles < big.vmem_limit_bytes <= pf.VMEM_MAX_BYTES
        with pytest.raises(ValueError, match=r"4096 x 4096 at a head width of 128"):
            pf._blocks(kernel, 4096, 4096, 4096, 4096, 128, jnp.bfloat16)
        # the measured pairs hold whole at the widths the models have ...
        for D, dtype in ((64, jnp.float32), (128, jnp.bfloat16), (256, jnp.bfloat16)):
            assert pf._blocks(kernel, None, None, 8192, 8192, D, dtype)[:2] == pf.DEFAULT_BLOCKS[kernel]
        # ... and give way, legal still, where a head is too wide for them
        bq, bk, _ = pf._blocks(kernel, None, None, 8192, 8192, 4096, jnp.float32)
        dq, dk = pf.DEFAULT_BLOCKS[kernel]
        assert bq * bk < dq * dk and bq % 8 == 0 and bk % 128 == 0
        assert pf._vmem_bytes(kernel, bq, bk, 4096, jnp.float32) <= pf.VMEM_MAX_BYTES
    # the refusal reaches a caller through the public function
    q = jnp.ones((1, 1, 4096, 128), jnp.bfloat16)
    with pytest.raises(ValueError, match="name smaller blocks"):
        flash_pl(q, q, q, None, True, 4096, 4096)


def test_kernel_bench_flash_rows_run_what_the_shape_declares():
    """scripts/kernel_bench.py: ``--flash-blocks`` entries and the operands
    of a flash row (the bench shape's dtype and causal flag, a padded-key
    bias), and the latent-attention bench shape is the cell's folded batch."""
    import importlib.util
    import pathlib

    from bcfl_tpu.ops.flash import FLASH_ATTENTION

    path = pathlib.Path(__file__).resolve().parents[1] / "scripts" / "kernel_bench.py"
    spec = importlib.util.spec_from_file_location("kernel_bench", path)
    kb = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(kb)

    assert kb._flash_variants("") == [("default", None)]
    assert kb._flash_variants("256,256;2048,2048/512,1024/1024,2048;default") == [
        ("256,256", {"fwd": (256, 256), "dkv": (256, 256), "dq": (256, 256)}),
        ("2048,2048/512,1024/1024,2048",
         {"fwd": (2048, 2048), "dkv": (512, 1024), "dq": (1024, 2048)}),
        ("default", None)]
    with pytest.raises(SystemExit):
        kb._flash_variants("256")

    (cell,) = [r for r in FLASH_ATTENTION.bench_shapes if r["D"] == 128]
    assert (cell["B"], cell["H"], cell["S"]) == (4, 32, 2048)
    assert cell["causal"] is True and cell["dtype"] == "bfloat16"
    tiny = dict(cell, B=3, H=1, S=64, D=8)
    (q, k, v, bias), kw = kb._build("flash_attention", tiny)
    assert q.dtype == k.dtype == v.dtype == jnp.bfloat16 and kw == {"causal": True}
    assert bias.shape == (3, 64) and bias.dtype == jnp.float32
    assert [int((row == 0).sum()) for row in bias] == [64, 56, 48]  # padded keys


def test_kernel_bench_scan_rows_run_what_the_model_hands_over():
    """scripts/kernel_bench.py: ``--scan-heads`` entries set the kernels'
    heads a grid step for a row and put the module's back; a scan row's
    ``x`` is ``[B, S, H P]``, as the model hands it over, and its timed
    program (forward, or forward and backward) runs the op on it."""
    import importlib.util
    import pathlib

    from bcfl_tpu.ops import pallas_ssm, registry
    from bcfl_tpu.ops.ssm_scan import SSM_SCAN

    path = pathlib.Path(__file__).resolve().parents[1] / "scripts" / "kernel_bench.py"
    spec = importlib.util.spec_from_file_location("kernel_bench", path)
    kb = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(kb)

    assert kb._scan_variants("") == [("default", None)]
    assert kb._scan_variants("16; 32;default") == [("16", 16), ("32", 32), ("default", None)]
    kept = pallas_ssm.HEAD_BLOCK
    with kb._scan_heads(8):
        assert pallas_ssm.HEAD_BLOCK == 8
    with kb._scan_heads(None):
        assert pallas_ssm.HEAD_BLOCK == kept
    assert pallas_ssm.HEAD_BLOCK == kept

    (cell,) = SSM_SCAN.bench_shapes
    tiny = dict(cell, B=2, S=32, H=4, P=8, N=16, chunk=32)
    args, kw = kb._build("ssm_scan", tiny)
    assert args[0].shape == (2, 32, 32) and args[0].dtype == jnp.bfloat16 and kw == {"chunk": 32}
    fn, impl = registry.select("ssm_scan", "pallas", jax.ShapeDtypeStruct((2, 32, 4, 8), jnp.bfloat16),
                               *args[1:], chunk=32)
    assert impl == "pallas"
    y = kb._scan_fn(fn, tiny, 32, False)(*args)
    assert y.shape == (2, 32, 4, 8)
    grads = kb._scan_fn(fn, tiny, 32, True)(*args)
    assert grads[0].shape == args[0].shape and bool(jnp.isfinite(grads[0].astype(jnp.float32)).all())


def test_kernel_bench_rule_rows_run_what_the_mixer_hands_over(tmp_path):
    """scripts/kernel_bench.py: a rule row's operands are drawn as the Gated
    DeltaNet mixer hands them over; ``--rule-forms`` entries run the op as it
    stands, the op with the product form of its inverse in place of the
    solve (put back afterwards), or another module file's rule, loaded
    without touching the registry; every form is timed against the first."""
    import importlib.util
    import pathlib
    import shutil

    from bcfl_tpu.ops import gated_delta, registry

    root = pathlib.Path(__file__).resolve().parents[1]
    spec = importlib.util.spec_from_file_location("kernel_bench", root / "scripts" / "kernel_bench.py")
    kb = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(kb)

    assert kb._rule_forms("") == ["default"]
    assert kb._rule_forms("default; product") == ["default", "product"]
    op = registry.get_op("gated_delta_rule")
    (cell,) = op.bench_shapes
    assert (cell["B"], cell["S"], cell["Hk"], cell["H"], cell["D"]) == (2, 8192, 16, 32, 128)
    tiny = dict(cell, S=96, Hk=1, H=2, D=16)
    args, kw = kb._build("gated_delta_rule", tiny)
    q, k, v, g, beta = args
    assert [a.dtype for a in args] == [jnp.bfloat16] * 3 + [jnp.float32] * 2 and kw == {"chunk": 64}
    assert q.shape == (2, 96, 1, 16) and v.shape == (2, 96, 2, 16) and g.shape == (2, 96, 2)
    np.testing.assert_allclose(jnp.linalg.norm(k.astype(jnp.float32), axis=-1), 1.0, atol=1e-2)
    assert bool((g < 0).all()) and bool(((beta > 0) & (beta < 1)).all())

    a = jnp.tril(0.3 * jax.random.normal(jax.random.key(0), (3, 64, 64)), -1)
    np.testing.assert_allclose(kb._product_form(a), gated_delta._unit_lower_inverse(a), atol=1e-5)
    kept = gated_delta._unit_lower_inverse
    with kb._rule_form("product") as fn:
        assert gated_delta._unit_lower_inverse is kb._product_inverse
        assert kb._solves(kb._rule_fn(fn, 64, True), args) == 0
    assert gated_delta._unit_lower_inverse is kept
    other = tmp_path / "other_rule.py"
    shutil.copy(root / "bcfl_tpu" / "ops" / "gated_delta.py", other)
    with kb._rule_form(str(other)) as fn:
        assert fn is not gated_delta.gated_delta_rule_xla
    assert registry.get_op("gated_delta_rule") is op

    rows = kb._rule_rows(op, args, kw, ["default", "product", str(other)], True, 1, {})
    assert [r["status"] for r in rows] == ["ok"] * 3
    assert [r.get("parity_ok") for r in rows] == [None, True, True]
    assert [r["triangular_solves"] for r in rows] == [3, 0, 3]
