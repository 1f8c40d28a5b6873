"""Pallas codec kernels + kernel harness contracts (PERF.md "Custom
kernels").

The codec's Pallas kernels (``bcfl_tpu.ops.pallas_codec``) run here in
interpret mode on CPU — the exact kernel bodies, off silicon — and are
held to their declared parity: **bit-identical** payloads against the
per-leaf XLA reference encode, for every codec kind, stochastic and
deterministic, across padded / odd-width / rank-2-adapter shapes.

Both sides of every parity check are jitted: XLA:CPU strength-reduces
``x / 127.0`` differently under jit than in eager (reciprocal-multiply vs
IEEE divide, a 1-ULP scale difference), so bit-identity is defined — and
production-relevant — within a compilation context. Round programs are
always jitted; a receiver authenticates the bytes it received and never
re-encodes, so cross-program identity is not a wire requirement.

Harness contracts ride along: unknown ops reject loudly, ``kernel_impl``
never reaches the wire format (resume may switch impls freely), a top-k
row past the VMEM budget is routed to the reference by a static shape
predicate before anything runs, and the interpret knob honors
``BCFL_PALLAS_INTERPRET`` (asking for compiled kernels off-TPU is an
error).
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bcfl_tpu.compression import (
    CompressionConfig,
    decode_tree,
    encode_tree,
    wire_format,
)
from bcfl_tpu.compression.codecs import encode_tree_unfused
from bcfl_tpu.config import FedConfig, PartitionConfig
from bcfl_tpu.fed.engine import FedEngine
from bcfl_tpu.ops import flash, pallas_codec, registry  # noqa: F401 — registers flash_attention

pytestmark = pytest.mark.compression


def _tree(seed=0):
    """Stacked [C=4, ...] leaves: chunk-padded odd widths, a bf16-typical
    small vector, an exact-chunk-multiple leaf, and a rank-2 LoRA adapter
    pair (in_features x r and r x out_features views, COMPRESSION.md) —
    ties, zeros, and -0.0 included so tie-breaking and sign-preserving
    select are exercised."""
    k = jax.random.key(seed)
    t = {
        "w": jax.random.normal(jax.random.fold_in(k, 1), (4, 37, 5)) * 3.0,
        "b": jax.random.normal(jax.random.fold_in(k, 2), (4, 9)),
        "exact": jax.random.normal(jax.random.fold_in(k, 3), (4, 64)),
        "lora_a": jax.random.normal(jax.random.fold_in(k, 4), (4, 48, 2)),
        "lora_b": jax.random.normal(jax.random.fold_in(k, 5), (4, 2, 48)),
    }
    w = np.array(t["w"])
    w[0, 0, :4] = [0.5, 0.5, -0.5, 0.0]  # magnitude ties + an exact zero
    w[1, 0, :2] = [-0.0, 0.0]            # signed zeros survive the select
    t["w"] = jnp.asarray(w)
    return t


def _jit_encode(fn, comp):
    return jax.jit(lambda d, k: fn(comp, d, k))


@pytest.mark.parametrize("kind", ["int8", "topk", "int8+topk"])
@pytest.mark.parametrize("stochastic", [False, True])
def test_pallas_encode_bit_identical(kind, stochastic):
    """kernel_impl="pallas" (interpret mode here) must produce payloads
    BIT-identical to the per-leaf pure-XLA reference encode — same dtypes,
    same bits, so ledger digests and checkpointed EF state cannot move
    with impl selection."""
    ref_comp = CompressionConfig(kind=kind, chunk=16, topk_frac=0.3,
                                 stochastic=stochastic)
    pl_comp = CompressionConfig(kind=kind, chunk=16, topk_frac=0.3,
                                stochastic=stochastic, kernel_impl="pallas")
    tree, key = _tree(), jax.random.key(7)
    a = _jit_encode(encode_tree_unfused, ref_comp)(tree, key)
    b = _jit_encode(encode_tree, pl_comp)(tree, key)
    assert (jax.tree_util.tree_structure(a)
            == jax.tree_util.tree_structure(b))
    for (pa, xa), (_, xb) in zip(
            jax.tree_util.tree_flatten_with_path(a)[0],
            jax.tree_util.tree_flatten_with_path(b)[0]):
        assert np.asarray(xa).dtype == np.asarray(xb).dtype, pa
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb),
                                      err_msg=str(pa))
    # and every impl decodes the same payload to the same tree
    dec_auto = decode_tree(ref_comp, b, tree)
    dec_pl = decode_tree(pl_comp, b, tree)
    for xa, xb in zip(jax.tree.leaves(dec_auto), jax.tree.leaves(dec_pl)):
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))


@pytest.mark.parametrize("impl", ["auto", "xla", "pallas"])
def test_every_impl_same_payload(impl):
    """The three selectable impls agree bit-for-bit on one jitted encode
    (int8+topk, stochastic — the full pipeline)."""
    comp = CompressionConfig(kind="int8+topk", chunk=16, topk_frac=0.25,
                             stochastic=True, kernel_impl=impl)
    ref = CompressionConfig(kind="int8+topk", chunk=16, topk_frac=0.25,
                            stochastic=True)  # default auto
    tree, key = _tree(3), jax.random.key(5)
    a = _jit_encode(encode_tree, ref)(tree, key)
    b = _jit_encode(encode_tree, comp)(tree, key)
    for xa, xb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))


def test_topk_width_predicate_agrees_with_dispatch(monkeypatch):
    """The static top-k width predicate IS the dispatch rule: for rows on
    both sides of the VMEM budget, ``_run_op`` calls the Pallas impl
    exactly where ``topk_supported`` says yes and the XLA reference where
    it says no, ``kernel_plan`` names the same impl per group, and the
    reference-served group is bit-identical. Called past the predicate,
    the kernel itself is a loud error, not a fallback."""
    from bcfl_tpu.compression import kernel_plan
    from bcfl_tpu.compression.codecs import _run_op

    widest = pallas_codec.TOPK_VMEM_BUDGET_BYTES // (
        8 * 4 * pallas_codec._TOPK_LIVE_BUFFERS)
    calls = []
    op = registry.get_op("topk_select")
    spy = lambda impl, fn: (  # noqa: E731
        lambda *a, **kw: calls.append(impl) or fn(*a, **kw))
    monkeypatch.setitem(registry._REGISTRY, "topk_select", dataclasses.replace(
        op, xla=spy("xla", op.xla), pallas=spy("pallas", op.pallas)))
    for n, fits in ((96, True), (widest, True), (widest + 1, False),
                    (60_000, False)):
        x = jax.ShapeDtypeStruct((8, n), jnp.float32)
        assert pallas_codec.topk_supported(x, k=5) is fits
        calls.clear()
        jax.eval_shape(lambda y: _run_op("topk_select", "pallas", y, k=5), x)
        assert calls == ["pallas" if fits else "xla"], (n, calls)
        comp = CompressionConfig(kind="topk", topk_frac=5 / n,
                                 kernel_impl="pallas")
        (impl,) = kernel_plan(
            comp, {"w": jax.ShapeDtypeStruct((n,), jnp.float32)},
            num_clients=8)["topk_select"].values()
        assert impl == ("pallas" if fits else "xla(unsupported shape)")
    monkeypatch.undo()

    x = jax.random.normal(jax.random.key(0), (8, 60_000), jnp.float32)
    with pytest.raises(ValueError, match="VMEM"):
        pallas_codec._topk_select_pallas(x, k=5)
    va, ia = jax.jit(lambda y: _run_op("topk_select", "xla", y, k=5))(x)
    vb, ib = jax.jit(lambda y: _run_op("topk_select", "pallas", y, k=5))(x)
    np.testing.assert_array_equal(np.asarray(va), np.asarray(vb))
    np.testing.assert_array_equal(np.asarray(ia), np.asarray(ib))


def test_kernel_plan_matches_fused_encode_groups():
    """``kernel_plan`` forms the groups exactly as ``encode_tree`` does:
    one op call per planned group, for every codec kind."""
    from bcfl_tpu.compression import kernel_plan

    tree = _tree()
    template = jax.tree.map(lambda x: x[0], tree)
    for kind in ("int8", "topk", "int8+topk"):
        comp = CompressionConfig(kind=kind, chunk=16, topk_frac=0.3)
        plan = kernel_plan(comp, template, num_clients=4)
        jaxpr = str(jax.make_jaxpr(
            lambda d, k: encode_tree(comp, d, k))(tree, jax.random.key(7)))
        assert jaxpr.count(" top_k[") == len(plan.get("topk_select", {}))
        assert jaxpr.count("convert_element_type[new_dtype=int8") == len(
            plan.get("int8_quantize", {}))


# ----------------------------------------------------------------- harness


def test_registry_rejects_undeclared_op():
    """Unknown op names reject loudly (the "reject nothing" rule is about
    impl degradation, never about typo'd ops); unknown impls too."""
    with pytest.raises(KeyError, match="unknown kernel op"):
        registry.resolve("definitely_not_registered")
    with pytest.raises(KeyError, match="int8_quantize"):
        # the error names the registered ops, so the typo is debuggable
        registry.get_op("int8_quantize_v2")
    with pytest.raises(ValueError, match="impl"):
        registry.resolve("int8_quantize", "cuda")
    with pytest.raises(ValueError, match="kernel_impl"):
        CompressionConfig(kind="int8", kernel_impl="cuda")


def test_auto_selects_pallas_only_where_one_tpu_chip_is_visible(monkeypatch):
    """Mosaic kernels cannot be partitioned automatically, so ``auto``
    means Pallas only in a process that sees ONE TPU device; on a
    multi-chip host the GSPMD round programs get the XLA references. An
    explicit "pallas" request is never rewritten."""
    monkeypatch.setattr(registry, "on_tpu", lambda: True)
    for count, want in ((1, "pallas"), (4, "xla")):
        monkeypatch.setattr(registry.jax, "device_count", lambda c=count: c)
        for name in ("int8_quantize", "topk_select", "flash_attention"):
            assert registry.resolve(name, "auto")[1] == want
            assert registry.resolve(name, "pallas")[1] == "pallas"


def test_registry_degrades_pallas_to_xla_for_xla_only_ops():
    """Explicit kernel_impl="pallas" on an op with no Pallas impl serves
    the XLA reference (decode-side ops are registered XLA-only)."""
    fn, resolved = registry.resolve("int8_dequant", "pallas")
    assert resolved == "xla"
    assert fn is registry.get_op("int8_dequant").xla
    # auto off-TPU is XLA even when a Pallas impl exists
    _, resolved = registry.resolve("int8_quantize", "auto")
    assert resolved == ("pallas" if registry.pallas_by_default() else "xla")


def test_interpret_knob(monkeypatch):
    monkeypatch.delenv(registry.INTERPRET_ENV, raising=False)
    # auto: interpret everywhere but on a real TPU backend
    assert registry.interpret_mode() == (jax.default_backend() != "tpu")
    monkeypatch.setenv(registry.INTERPRET_ENV, "1")
    assert registry.interpret_mode() is True
    # a compiled kernel off-TPU is an error, not a silent interpret...
    monkeypatch.setenv(registry.INTERPRET_ENV, "0")
    with pytest.raises(RuntimeError, match="compiled Pallas kernels"):
        registry.interpret_mode()
    # ...and on a TPU both values of the knob are honored
    monkeypatch.setattr(registry, "on_tpu", lambda: True)
    assert registry.interpret_mode() is False
    monkeypatch.setenv(registry.INTERPRET_ENV, "1")
    assert registry.interpret_mode() is True


def test_legal_block_sizes():
    """The shared Mosaic legalization: a block divides into the dim on the
    tile unit, or IS the dim (then any size is legal)."""
    assert registry.legal_block(256, 1024, 128) == 256
    assert registry.legal_block(2048, 1024, 128) == 1024  # clamp to dim
    assert registry.legal_block(200, 1024, 128) == 128    # floor to unit
    assert registry.legal_block(37, 37, 128) == 37        # == dim: legal
    assert registry.legal_block(64, 100, 128) == 100      # sub-unit dim
    assert registry.legal_block_sizes(
        ((512, 128, 8), (512, 384, 128))) == (128, 384)


# ------------------------------------------------------------ engine seam


def _tiny(**kw):
    base = dict(
        dataset="synthetic", model="tiny-bert", num_clients=4, num_rounds=2,
        seq_len=16, batch_size=4, max_local_batches=2, vocab_size=512,
        partition=PartitionConfig(kind="iid", iid_samples=8),
    )
    base.update(kw)
    return FedConfig(**base)


def test_kernel_impl_excluded_from_wire_format_and_resume(tmp_path):
    """kernel_impl is NOT codec identity: every impl's payload is byte-
    identical, so (a) wire_format strings are equal across impls and (b) a
    checkpointed run resumes under a DIFFERENT kernel_impl without the
    wire-format refusal — unlike a kind/chunk/topk_frac change."""
    comps = [CompressionConfig(kind="int8+topk", topk_frac=0.1,
                               kernel_impl=i) for i in ("auto", "xla",
                                                        "pallas")]
    assert len({wire_format(c) for c in comps}) == 1
    kw = dict(checkpoint_dir=str(tmp_path), checkpoint_every=1,
              eval_every=0)
    FedEngine(_tiny(num_rounds=1, compression=comps[1], **kw)).run()
    res = FedEngine(_tiny(num_rounds=2, compression=comps[2],
                          **kw)).run(resume=True)
    assert len(res.metrics.rounds) == 1  # resumed past round 0, no refusal
