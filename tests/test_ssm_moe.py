"""The state-space and attention hybrid decoder (``models/ssm_moe.py``), its
chunked scan (``ops/ssm_scan.py``) and its plain reference
(``benchmarks/families/ssm_moe``), at the tiny preset on the CPU, seeded
weights, float32 unless said.

Tolerances against the reference: 2e-4 relative. Both sides are float32,
but the program's chunked scan adds a row's positions in another order than
the reference's position-by-position loop (a masked product a chunk against
4 to 24 sequential updates), its router takes the softmax before the top k
and the reference after, and XLA:CPU's products add in an order of their
own; four layers of that stay under 2e-5 here, and 2e-4 leaves a factor of
ten."""

import collections
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from bcfl_tpu.config import FedConfig
from bcfl_tpu.fed.client_step import (make_local_train, make_loss_fn, make_optimizer,
                                      model_variables)
from bcfl_tpu.models import (FAMILIES, build, experts, family_of, get_config, lora, lora_policy,
                             tp_param_specs)
from bcfl_tpu.models import ssm_moe as sm
from bcfl_tpu.ops import pallas_ssm, registry
from bcfl_tpu.ops.ssm_scan import n_chunks, ssm_scan

from benchmarks import compare, harness
from benchmarks.families import ssm_moe as fam
from benchmarks.families.ssm_moe import flops, plain, weights

CELL = "granite-4.0-h-small.lora-r16-s4096"
SEED = 2147483659


@pytest.fixture(scope="module")
def sizes():
    """The configuration's file at its tiny preset, float32 throughout."""
    _, s = harness.load_cell(CELL, plumbing=True)
    return dict(s, training=dict(s["training"], param_dtype="float32", compute_dtype="float32"))


@pytest.fixture(scope="module")
def seeded(sizes):
    """``(model, adapters, frozen, flat)``: the program's model and trees from
    the family's weights."""
    flat = weights.make(sizes, SEED)
    adapters, frozen = weights.to_program(flat, sizes)
    p = fam.program(sizes)
    model = build(p["model"], head="lm", vocab_size=p["vocab_size"], dtype=jnp.float32,
                  param_dtype=jnp.float32, remat=True)
    return model, adapters, jax.tree.map(jnp.asarray, frozen), flat


def _batch(sizes, B=2, S=24, seed=0):
    rng = np.random.default_rng(seed)
    lengths = np.array([S, S - 7][:B])  # the second row ends in padding
    mask = (np.arange(S)[None] < lengths[:, None]).astype(np.int32)
    ids = np.where(mask > 0, rng.integers(4, sizes["vocab_rows"], (B, S)), 0).astype(np.int32)
    return {"ids": jnp.asarray(ids), "mask": jnp.asarray(mask),
            "example_mask": jnp.ones((B,), jnp.float32)}


# ----------------------------------------------------------- the chunked scan

def _scan_loop(x, dt, A, Bm, Cm, D):
    """The definition, a position at a time."""
    def row(x, dt, Bm, Cm):
        def step(S, t):
            xt, dtt, bt, ct = t
            S = (jnp.exp(dtt * A)[:, None, None] * S
                 + (dtt[:, None] * xt)[:, :, None] * bt[None, None, :])
            return S, (S * ct).sum(-1) + D[:, None] * xt

        return lax.scan(step, jnp.zeros(x.shape[1:] + (Bm.shape[-1],)), (x, dt, Bm, Cm))[1]

    return jax.vmap(row)(x, dt, Bm, Cm)


def _scan_inputs(S, dt_a, seed=0, H=4, P=8):
    """Heads whose ``dt A`` is about ``dt_a`` a position (spread 0.5x to 1.5x)."""
    k = jax.random.split(jax.random.key(seed), 6)
    b, N = 2, 16
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, S, H))) * 0.5 + 0.5  # around 1
    A = dt_a * jnp.linspace(0.5, 1.5, H)
    return (jax.random.normal(k[0], (b, S, H, P)), dt, A, jax.random.normal(k[2], (b, S, N)),
            jax.random.normal(k[3], (b, S, N)), jax.random.normal(k[4], (H,)))


#: (impl, S, chunk, heads): the jax.numpy form at chunks that divide the
#: row, one chunk, a tail chunk; the Pallas kernels (interpreted here) at
#: shapes they take (``pallas_ssm.supports``): three chunks of 128, one
#: chunk, and three chunks of the published 256 with a tail, where a fast
#: head's running sum reaches -4000 as in the cell, all in one head block of
#: one lane group (4 heads of 8); then split as the cell splits its heads
#: (heads of 64, two a lane group, ``HEAD_BLOCK`` 32 a grid step: 64 heads
#: make two head blocks of 16 lane groups, so the per-block state slots,
#: ``C B^T`` kept from the first block, ``dB`` and ``dC`` summed across the
#: blocks and the lane groups past the first are all run), and 64 heads of
#: 8 (two blocks of two lane groups of 16 heads)
SCAN_CASES = [("xla", 32, 8, (4, 8)), ("xla", 32, 32, (4, 8)), ("xla", 30, 8, (4, 8)),
              ("pallas", 384, 128, (4, 8)), ("pallas", 32, 32, (4, 8)),
              ("pallas", 600, 256, (4, 8)), ("pallas", 384, 128, (64, 64)),
              ("pallas", 384, 128, (64, 8))]
SCAN_IDS = ["chunks-divide", "one-chunk", "a-tail-chunk",
            "pallas-chunks-divide", "pallas-one-chunk", "pallas-a-tail-chunk",
            "pallas-head-blocks-as-the-cell", "pallas-head-blocks-of-two-lane-groups"]


@pytest.mark.parametrize("impl,S,chunk,heads", SCAN_CASES, ids=SCAN_IDS)
@pytest.mark.parametrize("dt_a", [-0.001, -1.0, -16.0], ids=["slow", "middling", "fast"])
def test_the_chunked_scan_against_a_position_by_position_loop(impl, S, chunk, heads, dt_a):
    """Values and every gradient. Slow heads (``dt A`` of -0.001: the state
    crosses every chunk boundary nearly whole), fast ones (-16: a chunk's
    running sum is -128 to -512 at chunks of 8 to 32 and -4000 at the
    published chunk; a quotient of exponentials would overflow, the
    difference does not). The kernels are held to the ``jax.numpy`` form as
    closely as to the loop."""
    args = _scan_inputs(S, dt_a, H=heads[0], P=heads[1])
    hb, W = pallas_ssm._geometry(*heads)
    split = (heads[0] // hb, hb * heads[1] // W)  # head blocks, lane groups a block
    assert split == {(64, 64): (2, 16), (64, 8): (2, 2)}.get(heads, (1, 1))
    assert registry.select("ssm_scan", impl, *args, chunk=chunk)[1] == impl
    t = jax.random.normal(jax.random.key(7), args[0].shape)
    with jax.default_matmul_precision("highest"):
        got = ssm_scan(*args, chunk=chunk, impl=impl)
        want = _scan_loop(*args)
        g_got = jax.grad(lambda *a: (ssm_scan(*a, chunk=chunk, impl=impl) * t).sum(), range(6))(*args)
        g_want = jax.grad(lambda *a: (_scan_loop(*a) * t).sum(), range(6))(*args)
        if impl == "pallas":
            g_xla = jax.grad(lambda *a: (ssm_scan(*a, chunk=chunk, impl="xla") * t).sum(),
                             range(6))(*args)
            np.testing.assert_allclose(got, ssm_scan(*args, chunk=chunk, impl="xla"),
                                       atol=2e-5 * float(jnp.abs(want).max()))
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, want, atol=2e-5 * float(jnp.abs(want).max()))
    # A's gradient sums position counts times decays: where a chunk's running
    # sum reaches -768 (fast heads, one chunk of 32) a difference of two such
    # sums carries 3e-5 of rounding, and the sum 5e-4 of its largest entry;
    # at chunks of 128 and 256 (sums of -3000 to -6000) the jax.numpy form
    # reads up to 1.0e-3 and the kernels, adding in another order, 1.8e-3
    for name, a, b in zip(("x", "dt", "A", "B", "C", "D"), g_got, g_want):
        assert bool(jnp.isfinite(a).all()), name
        room = (1e-3 if chunk <= 32 else 3e-3) if name == "A" else 1e-4
        np.testing.assert_allclose(a, b, atol=room * float(jnp.abs(b).max()) + 1e-30, err_msg=name)
        if impl == "pallas":
            c = g_xla["x dt A B C D".split().index(name)]
            np.testing.assert_allclose(a, c, atol=room * float(jnp.abs(c).max()) + 1e-30,
                                       err_msg=f"{name} against the jax.numpy form")


@pytest.mark.parametrize("impl,S,chunk", [("xla", 32, 8), ("pallas", 512, 128)], ids=["xla", "pallas"])
def test_the_state_crosses_the_chunk_boundaries(impl, S, chunk):
    """With slow heads a scan that started every chunk from zero would be far
    off: the test above can see a state carried wrongly."""
    args = _scan_inputs(S, -0.001)
    whole = ssm_scan(*args, chunk=chunk, impl=impl)
    x, dt, A, Bm, Cm, D = args
    cut = jnp.concatenate([ssm_scan(x[:, i:i + chunk], dt[:, i:i + chunk], A, Bm[:, i:i + chunk],
                                    Cm[:, i:i + chunk], D, chunk=chunk, impl=impl)
                           for i in range(0, S, chunk)], 1)
    np.testing.assert_allclose(cut[:, :chunk], whole[:, :chunk], atol=1e-5)
    assert float(jnp.abs(cut[:, chunk:] - whole[:, chunk:]).max()) > 0.5


def test_the_scan_is_in_the_kernel_registry():
    """Two implementations; ``auto`` is the Pallas one only where one TPU
    chip is visible, and a shape the kernels do not take (chunks that are
    no multiple of 128 in a row of several, heads wider than a lane group,
    or chunks whose kernels would need more VMEM than can be asked for) is
    served by the ``jax.numpy`` form, and says so before the call."""
    op = registry.get_op("ssm_scan")
    assert op.has_pallas and registry.resolve("ssm_scan", "pallas")[1] == "pallas"
    (shape,) = op.bench_shapes
    assert (shape["B"], shape["S"], shape["H"], shape["P"], shape["N"], shape["chunk"]) == (
        2, 4096, 128, 64, 128, 256)
    assert n_chunks(4096, 256) == 16 and n_chunks(4097, 256) == 17
    cell = [jax.ShapeDtypeStruct(s, d) for s, d in (
        ((2, 4096, 128, 64), jnp.bfloat16), ((2, 4096, 128), jnp.float32), ((128,), jnp.float32),
        ((2, 4096, 128), jnp.bfloat16), ((2, 4096, 128), jnp.bfloat16), ((128,), jnp.float32))]
    assert registry.select("ssm_scan", "pallas", *cell, chunk=256)[1] == "pallas"
    assert registry.select("ssm_scan", "auto", *cell, chunk=256)[1] == "xla"  # the CPU
    # chunks of 1024 at the cell's widths: the backward kernel would need
    # about 145 MiB of VMEM
    assert pallas_ssm._vmem_bytes("bwd", 1024, 32, 64, 128, 128, 128, 2) > pallas_ssm.VMEM_MAX_BYTES
    assert registry.select("ssm_scan", "pallas", *cell, chunk=1024)[1] == "xla(unsupported shape)"
    args = _scan_inputs(32, -1.0)
    assert registry.select("ssm_scan", "pallas", *args, chunk=8)[1] == "xla(unsupported shape)"
    wide = [jax.ShapeDtypeStruct((2, 32, 4, 96), jnp.float32)] + [
        jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args[1:]]
    assert registry.select("ssm_scan", "pallas", *wide, chunk=32)[1] == "xla(unsupported shape)"


@pytest.mark.parametrize("impl,S,chunk", [("xla", 64, 16), ("pallas", 384, 128)], ids=["xla", "pallas"])
def test_bfloat16_operands_float32_state(impl, S, chunk):
    """In the compute type the products round their operands and the decays
    and the carried state stay float32: close to the float32 scan by
    bfloat16's rounding, not by float32's; the kernels' rounding points are
    the ``jax.numpy`` form's, so the two agree to a rounding of the result
    and, on the mean, far closer than either does with the float32 scan."""
    args = _scan_inputs(S, -0.05)
    want = ssm_scan(*args, chunk=chunk)
    x, dt, A, Bm, Cm, D = args
    low = (x.astype(jnp.bfloat16), dt, A, Bm.astype(jnp.bfloat16), Cm.astype(jnp.bfloat16), D)
    got = ssm_scan(*low, chunk=chunk, impl=impl)
    assert got.dtype == jnp.bfloat16
    err = float(jnp.abs(got.astype(jnp.float32) - want).max() / jnp.abs(want).max())
    assert 1e-5 < err < 3e-2, err
    if impl == "pallas":  # at most one bfloat16 rounding of the largest value apart
        ref = ssm_scan(*low, chunk=chunk, impl="xla").astype(jnp.float32)
        apart = jnp.abs(got.astype(jnp.float32) - ref)
        assert float(apart.max() / jnp.abs(ref).max()) <= 2.0 ** -7
        assert float(apart.mean()) < float(jnp.abs(got.astype(jnp.float32) - want).mean()) / 4


def test_the_kernels_run_under_the_clients_vmap():
    """The round programs fold the clients with ``jax.vmap``: the kernels'
    batching rule adds a grid axis, and values and gradients are each
    client's own."""
    args = _scan_inputs(256, -1.0)
    clients = jax.tree.map(lambda a: jnp.stack([a, 0.5 * a]), args)

    def loss(impl):
        return lambda *a: (ssm_scan(*a, chunk=128, impl=impl) ** 2).sum()

    with jax.default_matmul_precision("highest"):
        got = jax.vmap(jax.value_and_grad(loss("pallas"), range(6)))(*clients)
        want = jax.vmap(jax.value_and_grad(loss("xla"), range(6)))(*clients)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4 * float(jnp.abs(b).max()))


# ------------------------------------------- the program against the reference

def test_logits_against_the_reference(sizes, seeded):
    model, adapters, frozen, flat = seeded
    b = _batch(sizes)
    got = model.apply(model_variables(model, adapters, frozen), b["ids"], b["mask"])
    want = plain.logits(weights.trained(flat), sizes, SEED, b)
    assert got.shape == (2, 24, sizes["vocab_rows"]) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=2e-4 * float(jnp.abs(want).max()), rtol=2e-4)


@pytest.mark.parametrize("kind,index", [("mamba", 0), ("attention", 2)])
def test_a_layer_against_the_reference(kind, index, sizes, seeded):
    """One layer of each kind (mixer, expert layer, both residual paths) on
    the same input."""
    model, adapters, frozen, flat = seeded
    cfg = model.cfg
    assert cfg.kinds[index] == kind
    b = _batch(sizes)
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, 24, cfg.hidden_size)), jnp.float32)
    key_bias = jnp.where(b["mask"] > 0, 0.0, -1e30).astype(jnp.float32)
    la = lora.as_collection(adapters)[f"layer_{index}"]
    got, _ = sm.SSMMoELayer(cfg, kind).apply(
        {"params": frozen[f"layer_{index}"], "lora": la}, x, sm.causal_bias(b["mask"]), key_bias,
        mutable=["counters"])
    want = plain.layer_fwd(x, weights.layer(sizes, SEED, index),
                           plain._of_layer(weights.trained(flat), index), b["mask"],
                           plain._static(sizes, kind, "f32"))
    np.testing.assert_allclose(got, want, atol=2e-4 * float(jnp.abs(want).max()), rtol=2e-4)


def test_loss_against_the_reference(sizes, seeded):
    model, adapters, frozen, flat = seeded
    b = _batch(sizes)
    loss, (correct, n, *counted) = make_loss_fn(model, "causal_lm")(adapters, frozen, b, None)
    want, n_ref, _ = plain.loss_and_grad(weights.trained(flat), sizes, SEED, b)
    assert float(n) == float(n_ref) == 23 + 16 and len(counted) == 4
    np.testing.assert_allclose(loss, want, rtol=2e-5)


def test_adapter_gradients_against_the_reference(sizes, seeded):
    model, adapters, frozen, flat = seeded
    b = _batch(sizes)
    loss_fn = make_loss_fn(model, "causal_lm")
    grads = jax.grad(lambda t: loss_fn(t, frozen, b, None)[0])(adapters)
    _, _, want = plain.loss_and_grad(weights.trained(flat), sizes, SEED, b)
    got = weights.from_program(grads, sizes)
    # three mamba layers of four adapted matrices, one attention layer of six, the head
    assert set(got) == set(want) and len(got) == 2 * (3 * 4 + 6 + 1)
    for k in want:
        scale = float(jnp.abs(want[k]).max())
        assert scale > 0, k  # a and b alike have a gradient in the first step
        np.testing.assert_allclose(got[k], want[k], atol=2e-4 * scale, rtol=2e-3, err_msg=k)


def test_a_federated_round_through_the_engine_against_the_reference(tmp_path):
    """``FedEngine.run`` on the normal fused path (ledger, donation, two
    rounds a dispatch, bfloat16 base) against the reference's rounds."""
    cell, sz = harness.load_cell(CELL, plumbing=True)
    run = harness.Run(cell, sz, SEED, 0.0, False, True, str(tmp_path), 0.0)
    engine = harness.setup_engine(run)
    assert engine.cfg.model == "tiny-ssm-moe@layers=4,experts_held=4"
    assert engine.cfg.lora_rank == 4 and engine.cfg.rounds_per_dispatch == 2
    assert {str(x.dtype) for x in jax.tree.leaves(engine.frozen)} == {"bfloat16"}
    assert {str(x.dtype) for x in jax.tree.leaves(engine.trainable0)} == {"float32"}
    assert "lm_head" in engine.trainable0 and "lm_head" not in engine.frozen  # tied
    res, recs, _ = harness._drive(run, 2)
    assert all(r.fused for r in recs) and res.metrics.ledger["chain_ok"] == 1.0
    ref = fam.reference(sz, SEED, run.batches, [r.mask for r in recs], run.n_ex)
    got = {k: np.asarray(v) for k, v in fam.from_program(jax.device_get(res.trainable), sz).items()}
    for r, want in zip(recs, ref["losses"]):
        assert abs(r.train_loss - want) / want < 1e-3
    gaps = compare.change_gaps(got, ref["trained"], ref["start"], ref["grad_norms"])
    assert gaps["worst"] < 0.05 and gaps["turn"] < 0.01 and not gaps["left_out"], gaps
    # the counters left the device with the statistics and reached the record
    c = recs[0].counters
    real = int(run.batches["mask"].sum())
    assert 0 < real < run.batches["mask"].size
    assert c["moe_slots_held"] + c["moe_slots_absent"] == real * sz["layers"] * sz["num_experts_per_tok"]
    t = cell["traffic"]
    rows = t["clients"] * t["local_batches"] * t["batch"]
    assert c["ssm_scan_chunks"] == rows * 3 * n_chunks(t["seq"], sz["mamba_chunk_size"])
    kids = res.metrics.phases["round_program"]["children"]["records"]
    assert kids["ssm_scan_chunks"] == sum(int(r.counters["ssm_scan_chunks"]) for r in recs)
    # what the rematerialised layers keep is on the run's first event
    with open(os.path.join(str(tmp_path), "telemetry", "events_engine.jsonl")) as f:
        start = next(e for e in map(json.loads, f) if e.get("ev") == "run.start")
    assert {k: start[k] for k in engine.remat_saved} == engine.remat_saved
    assert engine.remat_saved["remat_saved_mb_per_step"] > 0
    assert res.params is engine.frozen


def test_a_rows_tail_padding_changes_no_real_position(sizes, seeded):
    """Causal convolution, causal recurrence, causal attention: the logits of
    a row's real positions are those of the row cut at its last token."""
    model, adapters, frozen, _ = seeded
    b = _batch(sizes, B=2)
    variables = model_variables(model, adapters, frozen)
    padded = model.apply(variables, b["ids"], b["mask"])[1, :17]
    alone = model.apply(variables, b["ids"][1:, :17], b["mask"][1:, :17])[0]
    np.testing.assert_allclose(padded, alone, atol=2e-5)


# ------------------------------------- what a rematerialised layer keeps

def test_remat_changes_no_result(sizes, seeded):
    model, adapters, frozen, _ = seeded
    assert model.cfg.remat
    kept = build(fam.program(sizes)["model"], head="lm", vocab_size=sizes["vocab_rows"],
                 dtype=jnp.float32, param_dtype=jnp.float32, remat=False)
    b = _batch(sizes)
    out = [m.apply(model_variables(m, adapters, frozen), b["ids"], b["mask"],
                   mutable=["counters"])[0] for m in (model, kept)]
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(out[1]))
    grads = [jax.grad(lambda t, m=m: make_loss_fn(m, "causal_lm")(t, frozen, b, None)[0])(adapters)
             for m in (model, kept)]
    for path, g in jax.tree_util.tree_flatten_with_path(grads[0])[0]:
        want = grads[1]
        for k in path:
            want = want[k.key]
        np.testing.assert_allclose(np.asarray(g), np.asarray(want), rtol=0, atol=1e-6, err_msg=str(path))


def _one_layer(seeded, index, S, remat, flash=False):
    """Layer ``index`` of the seeded model as a function of its input and its
    adapters: ``(fn, x, adapters)``; ``remat``: "none", "plain"
    (``jax.checkpoint`` with no policy) or "named" (the model's own)."""
    model, adapters, frozen, _ = seeded
    cfg = model.cfg
    layer = sm.SSMMoELayer(cfg, cfg.kinds[index])
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, S, cfg.hidden_size)), cfg.dtype)
    mask = jnp.asarray(np.arange(S)[None] < np.array([S, S - 5])[:, None])
    key_bias = jnp.where(mask, 0.0, -1e30).astype(jnp.float32)
    bias = None if flash else sm.causal_bias(mask.astype(jnp.int32))
    la = lora.as_collection(adapters)[f"layer_{index}"]

    def fn(x, la):
        y, _ = layer.apply({"params": frozen[f"layer_{index}"], "lora": la}, x, bias, key_bias,
                           mutable=["counters"])
        return y.astype(jnp.float32).sum()

    if remat == "plain":
        fn = jax.checkpoint(fn)
    elif remat == "named":
        fn = jax.checkpoint(fn, policy=jax.checkpoint_policies.save_only_these_names(*sm.REMAT_SAVED))
    return fn, x, la


def _kept_shapes(cfg, kind, B, S, r, kernel=False):
    """``{(shape, dtype): how many}`` of the values ``REMAT_SAVED`` names in
    one layer of ``kind``."""
    dt, f32 = jnp.dtype(cfg.dtype), jnp.dtype("float32")
    H, KV, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    common = [((B, S, cfg.hidden_size), dt),  # the residual stream after the mixer
              ((B * S, cfg.num_local_experts), f32), ((B * S, cfg.num_experts_per_tok), jnp.dtype("int32")),
              ((B, S, 2 * cfg.shared_intermediate_size), dt)]
    if kind == "mamba":
        width = 2 * cfg.d_inner + 2 * cfg.mamba_d_state + cfg.mamba_n_heads
        mixer = [((B, S, r), f32)] * 4 + [((B, S, width), dt),
                                          ((B, S, cfg.mamba_n_heads, cfg.mamba_d_head), dt)]
    else:
        mixer = [((B, S, r), f32)] * 6 + [((B, H, S, D), dt)] + [((B, KV, S, D), dt)] * 2
        if kernel:  # the flash kernel's output and one lane of its log-sum-exp
            mixer += [((B, H, S, D), dt), ((B, H, S), f32)]
    return collections.Counter(mixer + common)


@pytest.mark.parametrize("index,path", [(0, "dense"), (2, "dense"), (2, "kernel")],
                         ids=["mamba", "attention", "attention-kernel"])
def test_a_rematerialised_layer_keeps_the_named_set_and_no_more(index, path, seeded, monkeypatch):
    from jax._src.ad_checkpoint import saved_residuals

    kernel = path == "kernel"
    if kernel:  # the Pallas kernels, interpreted on the CPU
        monkeypatch.setattr(registry, "pallas_by_default", lambda: True)
    S = 128 if kernel else 16
    fn, x, la = _one_layer(seeded, index, S, "named", flash=kernel)
    cfg = seeded[0].cfg
    want = _kept_shapes(cfg, cfg.kinds[index], 2, S, 4, kernel)
    got, handed_on = collections.Counter(), []
    for aval, why in saved_residuals(fn, x, la):
        if "from the argument" in why or "from a constant" in why:
            continue
        key = (tuple(aval.shape), jnp.dtype(aval.dtype))
        if "jitted function" in why:
            handed_on.append(key)
        else:
            got[key] += 1
    for key in handed_on:  # a kept value under a second name, or in place of its first
        if got[key] < want[key]:
            got[key] += 1
        else:
            assert key in want, (key, "kept beyond the named set")
    assert got == want


def _count(jaxpr, acc):
    """``[products, scans, pallas calls]`` of a jaxpr and the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        acc[0] += name in ("dot_general", "ragged_dot_general", "ragged_dot")
        acc[1] += name == "scan"
        acc[2] += name == "pallas_call"
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _count(sub, acc)
    return tuple(acc)


@pytest.mark.parametrize("index,S,flash", [(0, 16, False), (0, 8, False), (2, 128, True)],
                         ids=["mamba", "mamba-scan-kernel", "attention"])
def test_the_backward_pass_runs_no_product_kernel_or_scan_of_the_forward_again(
        index, S, flash, seeded, monkeypatch):
    """The jaxpr of one layer's gradient: under the model's policy it has the
    products, the scans and the Pallas calls of the layer that keeps
    everything, and no more; a ``jax.checkpoint`` with no policy has the
    forward's again. The state-space op: in ``jax.numpy`` (two chunks of 8)
    its forward scan and its backward pass's two; as the kernels (a row of
    one chunk, which they take) the forward kernel once and the backward
    pass's two, the states that enter the chunks and the chunks last to
    first, and no scan."""
    monkeypatch.setattr(registry, "pallas_by_default", lambda: True)
    counts = {}
    for remat in ("none", "plain", "named"):
        fn, x, la = _one_layer(seeded, index, S, remat, flash=flash)
        counts[remat] = _count(jax.make_jaxpr(jax.grad(fn, (0, 1)))(x, la).jaxpr, [0, 0, 0])
    assert counts["named"] == counts["none"], counts
    assert counts["plain"][0] > counts["none"][0], counts
    if flash or S == 8:
        assert counts["none"][2] == 3 and counts["plain"][2] == 4, counts
    else:
        assert counts["plain"][1] == counts["none"][1] + 1, counts
    if S == 8:  # the scans left are the expert layer's; the op adds none
        assert counts["plain"][1] == counts["none"][1], counts


# --------------------------------------------------------------- the pieces

def test_the_shares_add_up(sizes, seeded):
    """The four shares' routed parts, with the mixer and the shared MLP
    counted once, equal the UNCUT reference's layer: nothing is dropped,
    nothing stands in for an absent chip."""
    model, adapters, frozen, flat = seeded
    cfg = model.cfg
    whole = dict(sizes, experts_held=sizes["num_local_experts"])
    w = weights.layer(whole, SEED, 0)
    w = dict(w, eo=50.0 * w["eo"])  # at 0.02 the routed part would sit under the tolerance
    b = _batch(sizes)
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, 24, cfg.hidden_size)), jnp.float32)
    want = plain.layer_fwd(x, w, plain._of_layer(weights.trained(flat), 0), b["mask"],
                           plain._static(whole, "mamba", "f32"))
    key_bias = jnp.where(b["mask"] > 0, 0.0, -1e30).astype(jnp.float32)
    la = lora.as_collection(adapters)["layer_0"]
    F = cfg.intermediate_size

    def share(held, scale=1.0):
        c = get_config("tiny-ssm-moe", dtype=jnp.float32, experts_held=held)
        at = jnp.asarray(held)
        p = dict(frozen["layer_0"])
        p["moe"] = dict(p["moe"], experts_gate=w["ei"][at][..., :F], experts_up=w["ei"][at][..., F:],
                        experts_down=scale * w["eo"][at])
        y, state = sm.SSMMoELayer(c, "mamba").apply({"params": p, "lora": la}, x, None, key_bias,
                                                    mutable=["counters"])
        return y, state["counters"]["moe"]

    base, _ = share((0, 1), scale=0.0)  # the mixer and the shared MLP, no routed part
    total = base
    for s in range(4):
        y, c = share((2 * s, 2 * s + 1))
        total = total + (y - base)
        assert float(c["moe_slots_held"] + c["moe_slots_absent"]) == (24 + 17) * 3
    np.testing.assert_allclose(total, want, atol=2e-4 * float(jnp.abs(want).max()))
    assert float(jnp.abs(want - base).max()) > 50 * 2e-4 * float(jnp.abs(want).max())  # the routed part is there


def test_attention_without_positions_against_a_per_head_loop():
    """Grouped-query heads, the softmax scale ``attention_multiplier`` (not
    ``D^-0.5``), no position term: by hand, a head at a time."""
    cfg = get_config("tiny-ssm-moe", dtype=jnp.float32)
    B, S, H, KV, D = 2, 12, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    x = jax.random.normal(jax.random.key(0), (B, S, cfg.hidden_size))
    mask = jnp.asarray(np.arange(S)[None] < np.array([S, S - 4])[:, None]).astype(jnp.int32)
    attn = sm.NoPEAttention(cfg)
    args = (sm.causal_bias(mask), jnp.where(mask > 0, 0.0, -1e30))
    p = attn.init(jax.random.key(1), x, *args)["params"]
    got = attn.apply({"params": p}, x, *args)
    q, k, v = (x @ p[n]["kernel"] for n in ("q_proj", "k_proj", "v_proj"))
    out = np.zeros((B, S, H * D), np.float32)
    for b in range(B):
        for h in range(H):
            g = h // (H // KV)
            s = (q[b, :, h * D:(h + 1) * D] @ k[b, :, g * D:(g + 1) * D].T) * cfg.attention_multiplier
            ok = np.tril(np.ones((S, S), bool)) & (np.asarray(mask[b]) > 0)[None]
            out[b, :, h * D:(h + 1) * D] = jax.nn.softmax(jnp.where(ok, s, -1e30), -1) @ v[b, :, g * D:(g + 1) * D]
    np.testing.assert_allclose(got, out @ p["o_proj"]["kernel"], atol=2e-5)
    # no position enters: the last position's output does not change when the
    # positions before it change places
    full = (sm.causal_bias(jnp.ones_like(mask)), jnp.zeros(mask.shape))
    order = jnp.asarray([5, 2, 9, 0, 7, 1, 10, 3, 8, 6, 4, 11])
    np.testing.assert_allclose(attn.apply({"params": p}, x[:, order], *full)[:, -1],
                               attn.apply({"params": p}, x, *full)[:, -1], atol=2e-6)


def test_the_tied_head_and_the_multipliers(seeded):
    """``logits = (norm(x) E^T + (norm(x) a) b) / logits_scaling`` with E the
    embedding; the embedding's rows enter times ``embedding_multiplier``."""
    model, adapters, frozen, _ = seeded
    cfg = model.cfg
    assert "lm_head" not in frozen and set(adapters["lm_head"]) == {"a", "b"}
    x = jax.random.normal(jax.random.key(0), (2, 5, cfg.hidden_size))
    E = frozen["embed"]["embedding"]
    got = sm.TiedHead(cfg).apply({"lora": adapters["lm_head"]}, x, E)
    want = (x @ E.T + (x @ adapters["lm_head"]["a"]) @ adapters["lm_head"]["b"]) / cfg.logits_scaling
    np.testing.assert_allclose(got, want, atol=1e-5)
    pol = lora_policy(model)
    assert pol.tied == (("lm_head", "embed/embedding"),) and pol.on_activations
    made = lora.init_lora(jax.random.key(1), frozen, 4, targets=pol.targets,
                          head_modules=pol.head_modules, dtype=pol.adapter_dtype, tied=pol.tied)
    assert jax.tree.map(jnp.shape, made) == jax.tree.map(jnp.shape, adapters)
    assert {str(x.dtype) for x in jax.tree.leaves(made)} == {"float32"}


def test_the_recurrences_own_parameters_by_mamba_2s_draw(sizes):
    """``A`` in [1, 16], ``dt`` in [0.001, 0.1] (as ``softplus(dt_bias)``),
    the convolution within +-1/sqrt(d_conv): in the family's weights and in
    the program's own initialisers alike."""
    w = weights.layer(sizes, SEED, 0)
    model = build("tiny-ssm-moe", head="lm")
    ids = jnp.ones((1, 8), jnp.int32)
    own = model.init(jax.random.key(0), ids, ids)["params"]["layer_0"]["mamba"]
    for alog, dtb, conv, convb in ((w["alog"], w["dtb"], w["conv"], w["convb"]),
                                   (own["A_log"], own["dt_bias"], own["conv_kernel"], own["conv_bias"])):
        A, dt = np.exp(np.asarray(alog)), np.asarray(jax.nn.softplus(dtb))
        assert (A >= 1).all() and (A <= 16).all() and A.std() > 1
        assert (dt >= 0.99e-3).all() and (dt <= 0.101).all()
        assert np.abs(conv).max() <= 0.5 and np.abs(convb).max() <= 0.5 and np.abs(conv).max() > 0.3


def _block_inputs(C=2, N=24, k=10, E=72, H=128, F=128, G=18, seed=0):
    """10 distinct experts of 72 a row, 18 held (slot G = absent)."""
    ks = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(ks[0], (C, N, H)) * 0.5
    idx = jnp.argsort(jax.random.uniform(ks[1], (C, N, E)), -1)[..., :k]
    slot = jnp.where(idx < G, idx, G)
    cw = jax.nn.softmax(jax.random.normal(ks[2], (C, N, k)), -1)
    wg, wu = (jax.random.normal(ks[i], (G, H, F)) * 0.1 for i in (3, 4))
    wd = jax.random.normal(ks[5], (G, F, H)) * 0.1
    return x, slot, cw, wg, wu, wd


def _block_plain(x, slot, cw, wg, wu, wd):
    y = jnp.zeros_like(x)
    for e in range(wg.shape[0]):
        w_e = jnp.where(slot == e, cw, 0.0).sum(-1)
        y = y + w_e[:, None] * ((jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e])
    return y


@pytest.mark.parametrize("n_routed", [None, 72], ids=["chunks-of-a-quarter", "sized-by-the-held-share"])
def test_the_folded_expert_block_at_10_of_72_equals_a_per_client_loop(n_routed):
    """A quarter of the assignments fall on held experts: as much as a chunk
    of a quarter of the sorted assignments holds, so this draw takes a second
    chunk there; the layer's own block sizes its chunks by the held share (a
    quarter more than its mean) and takes one. Nothing is dropped either way."""
    x, slot, cw, wg, wu, wd = _block_inputs()
    held = int((slot < 18).sum())
    assert slot.size // 4 < held <= experts._chunk_rows(slot.size, 18, 72)
    # the two cells' folded steps: 8192 positions x 4 of 128 with 16 held, x 10 of 72 with 18 held
    assert experts._chunk_rows(8192 * 4, 16, 128) == 8192 == experts._chunk_rows(8192 * 4, 16, None)
    assert experts._chunk_rows(8192 * 10, 18, 72) == 25600 and experts._chunk_rows(40, 8, 8) == 40
    folded = jax.vmap(experts.block_for(n_routed), in_axes=(0, 0, 0, None, None, None))
    got = folded(x, slot, cw, wg, wu, wd)
    want = jnp.stack([_block_plain(x[c], slot[c], cw[c], wg, wu, wd) for c in range(2)])
    np.testing.assert_allclose(got, want, atol=2e-4)
    t = jax.random.normal(jax.random.key(9), got.shape)
    gx, gcw = jax.grad(lambda x_, cw_: (folded(x_, slot, cw_, wg, wu, wd) * t).sum(), (0, 1))(x, cw)
    wx, wcw = jax.grad(lambda x_, cw_: (jnp.stack([
        _block_plain(x_[c], slot[c], cw_[c], wg, wu, wd) for c in range(2)]) * t).sum(), (0, 1))(x, cw)
    np.testing.assert_allclose(gx, wx, atol=5e-4)
    np.testing.assert_allclose(gcw, wcw, atol=5e-4)


def test_the_counters_sum_over_steps_layers_and_clients(sizes, seeded):
    model, adapters, frozen, _ = seeded
    loss_fn = make_loss_fn(model, "causal_lm")
    assert loss_fn.counters == sm.COUNTERS == model.COUNTERS
    assert [k for _, k in sm.COUNTERS] == ["sum", "sum", "sum", "max"]  # sums first
    lt = make_local_train(make_optimizer("adamw", 1e-3), loss_fn)
    C, T, B, S = 2, 3, 2, 20
    rng = np.random.default_rng(1)
    batches = {"ids": jnp.asarray(rng.integers(4, 512, (C, T, B, S)), jnp.int32),
               "mask": jnp.ones((C, T, B, S), jnp.int32), "example_mask": jnp.ones((C, T, B))}
    _, stats = jax.jit(jax.vmap(lt, in_axes=(None, None, 0, 0)))(
        adapters, frozen, batches, jax.random.split(jax.random.key(0), C))
    assert stats.shape == (C, 7)
    held, absent, chunks, rows_max = np.asarray(stats[:, 3:]).T
    np.testing.assert_array_equal(held + absent, T * sizes["layers"] * B * S * 3)
    # three mamba layers, two rows a step, three chunks of 8 a row of 20
    np.testing.assert_array_equal(chunks, T * 3 * B * 3)
    assert (held > 0).all() and (absent > 0).all() and (rows_max <= B * S).all()


def test_the_required_operations_by_the_rule():
    """By hand at the published sizes: one period of 10 layers, 18 of 72
    experts, 25088 rows, rank 16, sequence 4096."""
    sz = json.load(open(os.path.join(harness.HERE, "configs", "granite-4.0-h-small.json")))
    cell = json.load(open(os.path.join(harness.HERE, "workloads", CELL + ".json")))
    mamba = 4096 * 16768 + 8192 * 4096 + 4 * 8448
    scan = 128.5 * 128 + 128.5 * 128 * 64 + 2 * 128 * 64 * 128
    attn = 2 * 4096 * 4096 + 2 * 4096 * 1024
    shared, router, expert = 4096 * 3072 + 1536 * 4096, 4096 * 72, 3 * 4096 * 768
    frozen = 2 * (9 * mamba + attn + 10 * (shared + router + expert * 10 * 18 / 72)) + 2 * 4096 * 25088
    adapters = 2 * 16 * (9 * ((4096 + 16768) + (8192 + 4096)) + 2 * (4096 + 4096) + 2 * (4096 + 1024)
                         + 10 * ((4096 + 3072) + (1536 + 4096)) + (4096 + 25088))
    acts = 9 * 2 * scan + 2 * 2048.5 * 32 * 256
    assert flops.forward_flops_per_token(sz, 4096) == pytest.approx(frozen + adapters + acts, rel=1e-12)
    assert flops.train_flops_per_token(sz, 4096) == pytest.approx(
        2 * frozen + 3 * (adapters + acts), rel=1e-12)
    by = flops.by_group(sz, 4096)
    assert by["state-space mixer"][0] / flops.forward_flops_per_token(sz, 4096) == pytest.approx(0.61, abs=0.01)
    assert flops.real_share(cell) == pytest.approx(0.781, abs=1e-3)
    assert flops.train_flops_per_token(sz, 4096, cell) < flops.train_flops_per_token(sz, 4096)
    assert fam.program(sz) == {
        "model": "granite-4.0-h-small@layers=10,experts_held=18", "vocab_size": 25088,
        "num_labels": 2, "task": "causal_lm", "lora_rank": 16, "remat": True, "use_flash": True}
    flop, byts = flops.ssm_scan_work(sz, 16)
    assert flop == 3 * 4096 * 2 * scan
    assert byts == 4096 * 3 * (8448 * 2 + 128 * 4) + 4096 * 2 * 8192 * 2 + 16 * 4 * 128 * 64 * 128 * 4
    assert flops.grouped_matmul_work(sz, 1000, 3) == (
        6 * 2.0 * 1000 * 4096 * 768, 6 * 3 * 18 * 4096 * 768 * 2 + 6 * 1000 * (4096 + 768) * 2)
    assert flops.flash_attention_work(sz, 4096, 2)[0] == 6 * 2.0 * 2 * 32 * (4096 * 4097 / 2) * 128


def test_the_published_model_by_eval_shape():
    """The cut's frozen parameters as the issue counts them (2,955.8 M), the
    published pattern, and the whole model's 32.2 B, by shapes alone."""
    cfg = get_config("granite-4.0-h-small")
    assert cfg.num_layers == 40 and cfg.kinds.count("mamba") == 36
    assert [i for i, k in enumerate(cfg.kinds) if k == "attention"] == [5, 15, 25, 35]
    ids = jnp.ones((1, 8), jnp.int32)

    def count(name, vocab):
        model = build(name, head="lm", vocab_size=vocab)
        return sum(math.prod(x.shape) for x in jax.tree.leaves(
            jax.eval_shape(model.init, jax.random.key(0), ids, ids)["params"]))

    cut = count("granite-4.0-h-small@layers=10,experts_held=18", 25088)
    assert cut == pytest.approx(2955.8e6, rel=1e-3)
    assert count("granite-4.0-h-small", 100352) == pytest.approx(32.2e9, rel=5e-3)
    assert get_config("granite-4.0-h-small@layers=10,experts_held=18").kinds == (
        ("mamba",) * 5 + ("attention",) + ("mamba",) * 4)


# ------------------------------------------------------- registry and refusals

def test_four_families_by_name_and_by_model():
    # four of the registry's families (a later one follows them)
    assert FAMILIES[:4] == ("encoder", "llama", "latent_moe", "ssm_moe")
    assert family_of("tiny-bert") == "encoder" and family_of("tiny-llama") == "llama"
    assert family_of("mistral-small-4@layers=8,experts_held=16") == "latent_moe"
    assert family_of("granite-4.0-h-small@layers=10,experts_held=18") == "ssm_moe"
    assert family_of(build("tiny-ssm-moe", head="lm")) == "ssm_moe"
    assert family_of(build("tiny-latent-moe", head="lm")) == "latent_moe"
    cfg = get_config("granite-4.0-h-small@layers=10,experts_held=18", vocab_size=25088)
    assert (cfg.num_layers, cfg.held, cfg.vocab_size) == (10, tuple(range(18)), 25088)
    assert (cfg.n_routed_experts, cfg.moe_intermediate_size, cfg.n_shared_experts) == (72, 768, 2)
    assert get_config("tiny-ssm-moe", experts_held=(2, 5)).held == (2, 5)
    with pytest.raises(KeyError, match="tiny-ssm-moe"):
        family_of("no-such-model")
    with pytest.raises(KeyError, match="experts_held"):
        get_config("tiny-ssm-moe@vocab_rows=256")
    with pytest.raises(ValueError, match="not a set of experts"):
        get_config("tiny-ssm-moe@experts_held=9").held
    with pytest.raises(ValueError, match="pattern"):
        get_config("tiny-ssm-moe@layers=5")
    with pytest.raises(ValueError, match="LM head only"):
        build("tiny-ssm-moe")
    with pytest.raises(NotImplementedError, match="encoder and llama"):
        tp_param_specs(build("tiny-ssm-moe", head="lm"), {})


@pytest.mark.parametrize("fields,why", [
    (dict(task="classification"), "LM head only"),
    (dict(lora_rank=0), "no weight-gradient pass"),
    (dict(tp=2, num_clients=2), "no tensor-parallel layout"),
    (dict(sp=2, num_clients=2), "no state-space scan carries"),
    (dict(lora_ranks="2,4", lora_rank=0, num_clients=2), "heterogeneous"),
])
def test_config_time_refusals(fields, why):
    base = dict(model="tiny-ssm-moe", task="causal_lm", lora_rank=4, vocab_size=512)
    FedConfig(**base)  # the pairing that runs
    with pytest.raises(ValueError, match="family ssm_moe.*" + why):
        FedConfig(**{**base, **fields})
