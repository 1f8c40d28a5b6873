"""The Gated DeltaNet and gated attention decoder with experts
(``models/delta_moe.py``) and its plain reference
(``benchmarks/families/delta_moe``), at the tiny preset on the CPU, seeded
weights, float32 unless said.

Tolerances against the reference: 2e-4 relative. Both sides are float32,
but the program's chunked rule adds a row's positions in another order than
the reference's position-by-position loop (a triangular solve and masked
products a chunk against up to 24 sequential updates), and XLA:CPU's
products add in an order of their own; four layers of that stay under 1e-5
here, and 2e-4 leaves a factor of twenty."""

import collections
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bcfl_tpu.config import FedConfig
from bcfl_tpu.fed.client_step import (make_local_train, make_loss_fn, make_optimizer,
                                      model_variables)
from bcfl_tpu.models import (FAMILIES, build, family_of, get_config, lora, lora_policy,
                             tp_param_specs)
from bcfl_tpu.models import delta_moe as dm
from bcfl_tpu.models import ssm_moe
from bcfl_tpu.ops import registry
from bcfl_tpu.ops.gated_delta import n_chunks

from benchmarks import compare, harness
from benchmarks.families import delta_moe as fam
from benchmarks.families.delta_moe import flops, plain, weights

CELL = "qwen3-next-80b-a3b.lora-r16-s8192"
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


# ------------------------------------------- the program against the reference

def test_logits_against_the_reference(sizes, seeded):
    model, adapters, frozen, flat = seeded
    b = _batch(sizes)
    got = model.apply(model_variables(model, adapters, frozen), b["ids"], b["mask"])
    want = plain.logits(weights.trained(flat), sizes, SEED, b)
    assert got.shape == (2, 24, sizes["vocab_rows"]) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=2e-4 * float(jnp.abs(want).max()), rtol=2e-4)


@pytest.mark.parametrize("kind,index", [(dm.LINEAR, 0), (dm.FULL, 3)])
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
    got, _ = dm.DeltaMoELayer(cfg, kind).apply(
        {"params": frozen[f"layer_{index}"], "lora": la}, x, dm.causal_bias(b["mask"]), key_bias,
        jnp.arange(24), mutable=["counters"])
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
    # three Gated DeltaNet layers of five adapted matrices, one attention
    # layer of seven, the head
    assert set(got) == set(want) and len(got) == 2 * (3 * 5 + 7 + 1)
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
    assert engine.cfg.model == "tiny-delta-moe@layers=4,experts_held=4"
    assert engine.cfg.lora_rank == 4 and engine.cfg.rounds_per_dispatch == 2
    assert {str(x.dtype) for x in jax.tree.leaves(engine.frozen)} == {"bfloat16"}
    assert {str(x.dtype) for x in jax.tree.leaves(engine.trainable0)} == {"float32"}
    assert "lm_head" in engine.trainable0 and "lm_head" in engine.frozen  # untied
    res, recs, _ = harness._drive(run, 2)
    assert all(r.fused for r in recs) and res.metrics.ledger["chain_ok"] == 1.0
    assert all(r.auth == [1.0, 1.0] for r in recs)
    ref = fam.reference(sz, SEED, run.batches, [r.mask for r in recs], run.n_ex)
    got = {k: np.asarray(v) for k, v in fam.from_program(jax.device_get(res.trainable), sz).items()}
    # a bfloat16 base and pipeline at the tiny preset's large weights: on five
    # seeds the losses read at most 2e-3 off and the change's turn at most
    # 0.063, where the reference in bfloat16 turns 0.035 to 0.049
    for r, want in zip(recs, ref["losses"]):
        assert abs(r.train_loss - want) / want < 5e-3
    gaps = compare.change_gaps(got, ref["trained"], ref["start"], ref["grad_norms"])
    assert gaps["turn"] < 0.15 and not gaps["left_out"], gaps
    # the counters left the device with the statistics and reached the record
    c = recs[0].counters
    real = int(run.batches["mask"].sum())
    assert 0 < real < run.batches["mask"].size
    assert c["moe_slots_held"] + c["moe_slots_absent"] == real * sz["layers"] * sz["num_experts_per_tok"]
    t = cell["traffic"]
    rows = t["clients"] * t["local_batches"] * t["batch"]
    assert c["gdn_chunks"] == rows * 3 * n_chunks(t["seq"], sz["gdn_chunk_size"])
    kids = res.metrics.phases["round_program"]["children"]["records"]
    assert kids["gdn_chunks"] == sum(int(r.counters["gdn_chunks"]) for r in recs)
    # what the rematerialised layers keep is on the run's first event
    with open(os.path.join(str(tmp_path), "telemetry", "events_engine.jsonl")) as f:
        start = next(e for e in map(json.loads, f) if e.get("ev") == "run.start")
    assert {k: start[k] for k in engine.remat_saved} == engine.remat_saved
    assert engine.remat_saved["remat_saved_mb_per_step"] > 0


def test_a_rows_tail_padding_changes_no_real_position(sizes, seeded):
    """Causal convolution, causal rule, causal attention: the logits of a
    row's real positions are those of the row cut at its last token."""
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
    layer = dm.DeltaMoELayer(cfg, cfg.kinds[index])
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, S, cfg.hidden_size)), cfg.dtype)
    mask = jnp.asarray(np.arange(S)[None] < np.array([S, S - 5])[:, None])
    key_bias = jnp.where(mask, 0.0, -1e30).astype(jnp.float32)
    bias = None if flash else dm.causal_bias(mask.astype(jnp.int32))
    la = lora.as_collection(adapters)[f"layer_{index}"]

    def fn(x, la):
        y, _ = layer.apply({"params": frozen[f"layer_{index}"], "lora": la}, x, bias, key_bias,
                           jnp.arange(S), mutable=["counters"])
        return y.astype(jnp.float32).sum()

    if remat == "plain":
        fn = jax.checkpoint(fn)
    elif remat == "named":
        fn = jax.checkpoint(fn, policy=jax.checkpoint_policies.save_only_these_names(*dm.REMAT_SAVED))
    return fn, x, la


def _kept_shapes(cfg, kind, B, S, r, kernel=False):
    """``{(shape, dtype): how many}`` of the values ``REMAT_SAVED`` names in
    one layer of ``kind``."""
    dt, f32 = jnp.dtype(cfg.dtype), jnp.dtype("float32")
    H, Hd = cfg.hidden_size, cfg.head_dim
    Fs = cfg.shared_expert_intermediate_size
    common = [((B, S, H), dt),  # the residual stream after the mixer
              ((B * S, cfg.num_experts), f32), ((B * S, cfg.num_experts_per_tok), jnp.dtype("int32")),
              ((B, S, Fs), dt), ((B, S, Fs), dt),  # the shared expert's gate and up products
              ((B, S, H), dt), ((B, S, 1), dt)]  # its output and its gate's product
    if kind == dm.LINEAR:
        Hk, Hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        Dk, Dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        mixer = [((B, S, r), f32)] * 5 + [((B, S, Hk * (2 * Dk) + 2 * Hv * Dv), dt),
                                          ((B, S, 2 * Hv), dt), ((B, S, Hv, Dv), dt)]
    else:
        heads, kv = cfg.num_heads, cfg.num_kv_heads
        mixer = [((B, S, r), f32)] * 7 + [((B, S, heads * 2 * Hd), dt)] + [((B, S, kv * Hd), dt)] * 2
        if kernel:  # the flash kernel's output and one lane of its log-sum-exp
            mixer += [((B, heads, S, Hd), dt), ((B, heads, S), f32)]
    return collections.Counter(mixer + common)


@pytest.mark.parametrize("index,path", [(0, "dense"), (3, "kernel")],
                         ids=["gated-deltanet", "attention-kernel"])
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
        acc[0] += name in ("dot_general", "ragged_dot_general", "ragged_dot", "triangular_solve")
        acc[1] += name == "scan"
        acc[2] += name == "pallas_call"
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _count(sub, acc)
    return tuple(acc)


@pytest.mark.parametrize("index,S,flash", [(0, 16, False), (3, 128, True)],
                         ids=["gated-deltanet", "attention"])
def test_the_backward_pass_runs_no_product_kernel_or_rule_of_the_forward_again(
        index, S, flash, seeded, monkeypatch):
    """The jaxpr of one layer's gradient: under the model's policy it has the
    products, the scans and the Pallas calls of the layer that keeps
    everything, and no more; a ``jax.checkpoint`` with no policy has the
    forward's again (the rule's forward scan among them)."""
    monkeypatch.setattr(registry, "pallas_by_default", lambda: True)
    counts = {}
    for remat in ("none", "plain", "named"):
        fn, x, la = _one_layer(seeded, index, S, remat, flash=flash)
        counts[remat] = _count(jax.make_jaxpr(jax.grad(fn, (0, 1)))(x, la).jaxpr, [0, 0, 0])
    assert counts["named"] == counts["none"], counts
    assert counts["plain"][0] > counts["none"][0], counts
    if flash:
        assert counts["none"][2] == 3 and counts["plain"][2] == 4, counts
    else:
        assert counts["plain"][1] == counts["none"][1] + 1, counts


# --------------------------------------------------------------- the pieces

def test_the_shares_add_up(sizes, seeded):
    """The four shares' routed parts, with the mixer and the gated shared
    expert counted once, equal the UNCUT reference's layer: nothing is
    dropped, nothing stands in for an absent chip."""
    model, adapters, frozen, flat = seeded
    cfg = model.cfg
    whole = dict(sizes, experts_held=sizes["num_experts"])
    w = weights.layer(whole, SEED, 0)
    w = dict(w, ed=50.0 * w["ed"])  # at 0.15 the routed part would sit near the tolerance
    b = _batch(sizes)
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, 24, cfg.hidden_size)), jnp.float32)
    want = plain.layer_fwd(x, w, plain._of_layer(weights.trained(flat), 0), b["mask"],
                           plain._static(whole, dm.LINEAR, "f32"))
    key_bias = jnp.where(b["mask"] > 0, 0.0, -1e30).astype(jnp.float32)
    la = lora.as_collection(adapters)["layer_0"]

    def share(held, scale=1.0):
        c = get_config("tiny-delta-moe", dtype=jnp.float32, experts_held=held)
        at = jnp.asarray(held)
        p = dict(frozen["layer_0"])
        p["moe"] = dict(p["moe"], experts_gate=w["eg"][at], experts_up=w["eu"][at],
                        experts_down=scale * w["ed"][at])
        y, state = dm.DeltaMoELayer(c, dm.LINEAR).apply(
            {"params": p, "lora": la}, x, None, key_bias, jnp.arange(24), mutable=["counters"])
        return y, state["counters"]["moe"]

    base, _ = share((0, 1), scale=0.0)  # the mixer and the shared expert, no routed part
    total = base
    for s in range(4):
        y, c = share((2 * s, 2 * s + 1))
        total = total + (y - base)
        assert float(c["moe_slots_held"] + c["moe_slots_absent"]) == (24 + 17) * 3
    np.testing.assert_allclose(total, want, atol=2e-4 * float(jnp.abs(want).max()))
    assert float(jnp.abs(want - base).max()) > 50 * 2e-4 * float(jnp.abs(want).max())  # the routed part is there


def test_gated_attention_against_a_per_head_loop():
    """Zero-centred norms on q and k a head, rotary (rotate-half) on the
    first quarter of a head, grouped-query heads at scale ``D^-0.5``, the
    output times ``sigmoid(gate)`` with q and the gate side by side a head
    in ``q_proj``: by hand, a head at a time."""
    cfg = get_config("tiny-delta-moe", dtype=jnp.float32)
    B, S, H, KV, D, R = 2, 12, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.rotary_dim
    assert (D, R) == (16, 4)
    x = jax.random.normal(jax.random.key(0), (B, S, cfg.hidden_size))
    mask = jnp.asarray(np.arange(S)[None] < np.array([S, S - 4])[:, None]).astype(jnp.int32)
    attn = dm.GatedAttention(cfg)
    args = (dm.causal_bias(mask), jnp.where(mask > 0, 0.0, -1e30), jnp.arange(S))
    p = attn.init(jax.random.key(1), x, *args)["params"]
    p = dict(p, q_norm={"weight": 0.3 * jnp.arange(D) / D}, k_norm={"weight": -0.2 * jnp.ones(D)})
    got = attn.apply({"params": p}, x, *args)
    x64 = np.asarray(x, np.float64)
    qg = (x64 @ np.asarray(p["q_proj"]["kernel"])).reshape(B, S, H, 2 * D)
    k = (x64 @ np.asarray(p["k_proj"]["kernel"])).reshape(B, S, KV, D)
    v = (x64 @ np.asarray(p["v_proj"]["kernel"])).reshape(B, S, KV, D)

    def norm(a, w):
        return a / np.sqrt((a * a).mean(-1, keepdims=True) + cfg.rms_eps) * (1 + np.asarray(w))

    def turn(a):
        inv = 1.0 / cfg.rope_theta ** (np.arange(0, R, 2) / R)
        ang = np.arange(S)[:, None] * inv[None]
        cos, sin = np.cos(np.concatenate([ang, ang], -1)), np.sin(np.concatenate([ang, ang], -1))
        r = a[..., :R]
        half = np.concatenate([-r[..., R // 2:], r[..., :R // 2]], -1)
        return np.concatenate([r * cos[:, None] + half * sin[:, None], a[..., R:]], -1)

    q = turn(norm(qg[..., :D], p["q_norm"]["weight"]))
    k = turn(norm(k, p["k_norm"]["weight"]))
    out = np.zeros((B, S, H, D))
    for b in range(B):
        for h in range(H):
            g = h // (H // KV)
            s = q[b, :, h] @ k[b, :, g].T / math.sqrt(D)
            ok = np.tril(np.ones((S, S), bool)) & (np.asarray(mask[b]) > 0)[None]
            s = np.where(ok, s, -1e30)
            e = np.exp(s - s.max(-1, keepdims=True))
            out[b, :, h] = (e / e.sum(-1, keepdims=True)) @ v[b, :, g]
    out = out / (1 + np.exp(-qg[..., D:]))
    want = out.reshape(B, S, H * D) @ np.asarray(p["o_proj"]["kernel"])
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_causal_convolution_with_and_without_bias():
    """The one depthwise causal convolution both mixers run
    (``ssm_moe.causal_conv``): ``kernel[k]`` weighs the position ``K - 1 - k``
    back; positions before a row's first read 0."""
    x = jax.random.normal(jax.random.key(0), (2, 9, 6))
    kernel = jax.random.normal(jax.random.key(1), (4, 6))
    bias = jax.random.normal(jax.random.key(2), (6,))
    xp = np.pad(np.asarray(x), ((0, 0), (3, 0), (0, 0)))
    want = sum(np.asarray(kernel)[k] * xp[:, k:k + 9] for k in range(4))
    np.testing.assert_allclose(ssm_moe.causal_conv(x, kernel), want, atol=1e-5)
    np.testing.assert_allclose(ssm_moe.causal_conv(x, kernel, bias), want + np.asarray(bias), atol=1e-5)
    assert ssm_moe.causal_conv(x.astype(jnp.bfloat16), kernel).dtype == jnp.float32


def test_the_layer_pattern_rotary_share_and_the_rules_own_parameters(sizes):
    """Full attention every fourth layer; rotary on 64 of 256; the rule's
    ``A`` in (0, 16) and ``dt`` in [0.001, 0.1] (as ``softplus(dt_bias)``),
    the convolution within +-1/2, in the family's weights and in the
    program's own initialisers alike."""
    cfg = get_config("qwen3-next-80b-a3b")
    assert cfg.num_layers == 48 and cfg.kinds.count(dm.LINEAR) == 36
    assert [i for i, k in enumerate(cfg.kinds) if k == dm.FULL] == list(range(3, 48, 4))
    assert (cfg.head_dim, cfg.rotary_dim) == (256, 64)
    assert get_config("qwen3-next-80b-a3b@layers=4,experts_held=64").kinds == (
        (dm.LINEAR,) * 3 + (dm.FULL,))
    assert weights.kinds(dict(sizes, layers=8)) == ((dm.LINEAR,) * 3 + (dm.FULL,)) * 2
    w = weights.layer(sizes, SEED, 0)
    model = build("tiny-delta-moe", head="lm")
    ids = jnp.ones((1, 8), jnp.int32)
    own = model.init(jax.random.key(0), ids, ids)["params"]["layer_0"]["linear_attn"]
    for alog, dtb, conv in ((w["alog"], w["dtb"], w["conv"]),
                            (own["A_log"], own["dt_bias"], own["conv_kernel"])):
        A, dt = np.exp(np.asarray(alog)), np.asarray(jax.nn.softplus(dtb))
        assert (A > 0).all() and (A <= 16).all()
        assert (dt >= 0.99e-3).all() and (dt <= 0.101).all()
        assert np.abs(conv).max() <= 0.5 and np.abs(conv).max() > 0.3
    assert "conv_bias" not in own and float(np.asarray(w["gn"]).min()) == 1.0
    assert float(np.abs(np.asarray(w["n1"])).max()) == 0.0  # zero-centred: (1 + w), w at 0


def test_the_counters_sum_over_steps_layers_and_clients(sizes, seeded):
    model, adapters, frozen, _ = seeded
    loss_fn = make_loss_fn(model, "causal_lm")
    assert loss_fn.counters == dm.COUNTERS == model.COUNTERS
    assert [k for _, k in dm.COUNTERS] == ["sum", "sum", "sum", "max"]  # sums first
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
    # three Gated DeltaNet layers, two rows a step, three chunks of 8 a row of 20
    np.testing.assert_array_equal(chunks, T * 3 * B * 3)
    assert (held > 0).all() and (absent > 0).all() and (rows_max <= B * S).all()


def test_the_required_operations_by_the_rule():
    """By hand at the published sizes: one period of 4 layers, 64 of 512
    experts, 18992 rows, rank 16, sequence 8192."""
    sz = json.load(open(os.path.join(harness.HERE, "configs", "qwen3-next-80b-a3b.json")))
    cell = json.load(open(os.path.join(harness.HERE, "workloads", CELL + ".json")))
    gdn = 2048 * 12288 + 2048 * 64 + 4096 * 2048 + 4 * 8192
    rule = 32 * (5 * 2 * 32.5 * 128 + 64 ** 2 / 3 + 3 * 2 * 128 * 128)
    attn = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048
    shared, router, expert = 3 * 2048 * 512 + 2048, 2048 * 512, 3 * 2048 * 512
    frozen = 2 * (3 * gdn + attn + 4 * (shared + router + expert * 10 * 64 / 512)) + 2 * 2048 * 18992
    adapters = 2 * 16 * (3 * ((2048 + 12288) + (4096 + 2048)) + (2048 + 8192) + 2 * (2048 + 512)
                         + (4096 + 2048) + 4 * 3 * (2048 + 512) + (2048 + 18992))
    acts = 3 * rule + 2 * 4096.5 * 16 * 2 * 256
    assert flops.forward_flops_per_token(sz, 8192) == pytest.approx(frozen + adapters + acts, rel=1e-12)
    assert flops.train_flops_per_token(sz, 8192) == pytest.approx(
        2 * frozen + 3 * (adapters + acts), rel=1e-12)
    by = flops.by_group(sz, 8192)
    assert by["Gated DeltaNet mixer"][0] / flops.forward_flops_per_token(sz, 8192) == pytest.approx(
        0.445, abs=0.001)
    assert flops.real_share(cell) == pytest.approx(0.781, abs=1e-3)
    assert fam.program(sz) == {
        "model": "qwen3-next-80b-a3b@layers=4,experts_held=64", "vocab_size": 18992,
        "num_labels": 2, "task": "causal_lm", "lora_rank": 16, "remat": True, "use_flash": True}
    flop, byts = flops.gated_delta_work(sz, 128)
    assert flop == 3 * 8192 * rule
    row = (2 * 16 * 128 + 32 * 128) * 2 + 2 * 32 * 4
    assert byts == 8192 * (3 * row + 2 * 32 * 128 * 2) + 128 * 4 * 32 * 128 * 128 * 4


def test_the_published_model_by_eval_shape():
    """The cut's frozen parameters as the cut's sizing counts them (1,028,320,320)
    and the whole model's 79.7 B, by shapes alone."""
    ids = jnp.ones((1, 8), jnp.int32)

    def count(name, vocab):
        model = build(name, head="lm", vocab_size=vocab)
        return sum(math.prod(x.shape) for x in jax.tree.leaves(
            jax.eval_shape(model.init, jax.random.key(0), ids, ids)["params"]))

    assert count("qwen3-next-80b-a3b@layers=4,experts_held=64", 18992) == 1_028_320_320
    assert count("qwen3-next-80b-a3b", 151936) == pytest.approx(79.7e9, rel=1e-3)


# ------------------------------------------------------- registry and refusals

def test_five_families_by_name_and_by_model():
    assert FAMILIES == ("encoder", "llama", "latent_moe", "ssm_moe", "delta_moe")
    assert family_of("qwen3-next-80b-a3b@layers=4,experts_held=64") == "delta_moe"
    assert family_of(build("tiny-delta-moe", head="lm")) == "delta_moe"
    cfg = get_config("qwen3-next-80b-a3b@layers=4,experts_held=64", vocab_size=18992)
    assert (cfg.num_layers, cfg.held, cfg.vocab_size) == (4, tuple(range(64)), 18992)
    assert (cfg.n_routed_experts, cfg.moe_intermediate_size, cfg.n_shared_experts) == (512, 512, 1)
    assert cfg.shared_expert_gate and not getattr(get_config("tiny-ssm-moe"), "shared_expert_gate", False)
    pol = lora_policy("tiny-delta-moe")
    assert pol.on_activations and pol.adapter_dtype == "float32" and pol.tied == ()
    assert "in_proj_ba" not in pol.targets and "lm_head" in pol.targets
    with pytest.raises(ValueError, match="pattern"):
        get_config("tiny-delta-moe@layers=5")
    with pytest.raises(ValueError, match="LM head only"):
        build("tiny-delta-moe")
    with pytest.raises(NotImplementedError, match="encoder and llama"):
        tp_param_specs(build("tiny-delta-moe", head="lm"), {})


def test_a_family_without_a_lora_policy_is_refused(monkeypatch):
    """Every family names its policy; one the registry does not know is an
    error, not another family's policy."""
    import bcfl_tpu.models as models

    monkeypatch.setattr(models, "family_of", lambda m: "no_such_family")
    with pytest.raises(KeyError, match="no LoRA policy for the no_such_family family"):
        models.lora_policy("x")


@pytest.mark.parametrize("fields,why", [
    (dict(task="classification"), "LM head only"),
    (dict(lora_rank=0), "no weight-gradient pass"),
    (dict(tp=2, num_clients=2), "Gated DeltaNet mixer"),
    (dict(sp=2, num_clients=2), "nor does the gated delta rule"),
])
def test_config_time_refusals(fields, why):
    base = dict(model="tiny-delta-moe", task="causal_lm", lora_rank=4, vocab_size=512)
    FedConfig(**base)  # the pairing that runs
    with pytest.raises(ValueError, match="family delta_moe.*" + why):
        FedConfig(**{**base, **fields})
