"""The latent-attention expert decoder (bcfl_tpu/models/latent_moe.py) at
its tiny preset on the CPU: against the benchmark family's plain reference
(benchmarks/families/latent_moe, which imports nothing of the program) on
weights from the seed; the pieces against hand-written loops and hand values;
the shares of the experts against the uncut layer; the clients' fold; the
lowered step's operations; the counters; the config-time refusals."""

import collections
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bcfl_tpu.config import FedConfig
from bcfl_tpu.fed.client_step import (make_local_train, make_loss_fn,
                                      make_optimizer, model_variables)
from bcfl_tpu.models import (build, family_of, get_config, lora, lora_policy,
                             tp_param_specs)
from bcfl_tpu.models import experts
from bcfl_tpu.models import latent_moe as lm
from bcfl_tpu.ops.grouped_matmul import grouped_matmul

from benchmarks import compare, harness
from benchmarks.families import latent_moe as fam
from benchmarks.families.latent_moe import flops, plain, weights

CELL = "mistral-small-4.lora-r16-s2048"
SEED = 2147483659


@pytest.fixture(scope="module")
def sizes():
    """The configuration's file at its tiny preset, float32 throughout."""
    _, s = harness.load_cell(CELL, plumbing=True)
    s = dict(s, training=dict(s["training"], param_dtype="float32", compute_dtype="float32"))
    return s


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


def _batch(sizes, B=2, S=16, seed=0):
    rng = np.random.default_rng(seed)
    lengths = np.array([S, S - 5][:B])  # the second row ends in padding
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
    assert got.shape == (2, 16, sizes["vocab_rows"]) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-4)


def test_the_reference_takes_an_expert_in_several_passes(sizes, seeded, monkeypatch):
    """Where an expert has more rows than the reference's ``cap`` it goes
    again: with a cap of 4 rows (of 24) the logits are the program's still."""
    model, adapters, frozen, flat = seeded
    monkeypatch.setattr(plain, "rows_cap", lambda T, k, E: 4)
    b = _batch(sizes, S=12)  # a shape of its own: traced under the small cap
    got = model.apply(model_variables(model, adapters, frozen), b["ids"], b["mask"])
    want = plain.logits(weights.trained(flat), sizes, SEED, b)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-4)
    _, _, grads = plain.loss_and_grad(weights.trained(flat), sizes, SEED, b)
    assert all(np.isfinite(np.asarray(g)).all() for g in grads.values())


def test_loss_against_the_reference(sizes, seeded):
    model, adapters, frozen, flat = seeded
    b = _batch(sizes)
    loss, (correct, n, *counted) = make_loss_fn(model, "causal_lm")(adapters, frozen, b, None)
    want, n_ref, _ = plain.loss_and_grad(weights.trained(flat), sizes, SEED, b)
    assert float(n) == float(n_ref) == 15 + 10 and len(counted) == 3
    np.testing.assert_allclose(loss, want, rtol=1e-5)


def test_adapter_gradients_against_the_reference(sizes, seeded):
    model, adapters, frozen, flat = seeded
    b = _batch(sizes)
    loss_fn = make_loss_fn(model, "causal_lm")
    grads = jax.grad(lambda t: loss_fn(t, frozen, b, None)[0])(adapters)
    _, _, want = plain.loss_and_grad(weights.trained(flat), sizes, SEED, b)
    got = weights.from_program(grads, sizes)
    assert set(got) == set(want) and len(got) == 2 * (8 * sizes["layers"] + 1)
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
    assert engine.cfg.lora_rank == 4 and engine.cfg.rounds_per_dispatch == 2
    assert {str(x.dtype) for x in jax.tree.leaves(engine.frozen)} == {"bfloat16"}
    assert {str(x.dtype) for x in jax.tree.leaves(engine.trainable0)} == {"float32"}
    res, recs, _ = harness._drive(run, 2)
    assert all(r.fused for r in recs) and res.metrics.ledger["chain_ok"] == 1.0
    ref = fam.reference(sz, SEED, run.batches, [r.mask for r in recs], run.n_ex)
    got = {k: np.asarray(v) for k, v in fam.from_program(jax.device_get(res.trainable), sz).items()}
    for r, want in zip(recs, ref["losses"]):
        assert abs(r.train_loss - want) / want < 1e-3
    # the parameters' change as the benchmark compares it (bfloat16 products
    # against the float32 reference): every leaf's norm, and the direction
    gaps = compare.change_gaps(got, ref["trained"], ref["start"], ref["grad_norms"])
    assert gaps["worst"] < 0.05 and gaps["turn"] < 0.01 and not gaps["left_out"], gaps
    # the counters left the device with the statistics and reached the record
    c = recs[0].counters
    # every REAL position's assignments, in every layer (padded ones are routed nowhere)
    real = int(run.batches["mask"].sum())
    assert 0 < real < run.batches["mask"].size
    assert c["moe_slots_held"] + c["moe_slots_absent"] == real * sz["layers"] * sz["num_experts_per_tok"]
    assert 0 < c["moe_rows_max"] <= 2 * 16
    kids = res.metrics.phases["round_program"]["children"]["records"]
    assert kids["moe_slots_held"] == sum(int(r.counters["moe_slots_held"]) for r in recs)
    # what the rematerialised layers keep is on the run's first event
    with open(os.path.join(str(tmp_path), "telemetry", "events_engine.jsonl")) as f:
        start = next(e for e in map(json.loads, f) if e.get("ev") == "run.start")
    assert {k: start[k] for k in engine.remat_saved} == engine.remat_saved
    # the run's parameters are the base; the adapters are beside it
    assert res.params is engine.frozen


# ------------------------------------- what a rematerialised layer keeps


def test_remat_changes_no_result(sizes, seeded):
    """``remat=True`` (the named save set) against ``remat=False``
    (everything kept): the logits bit for bit, every adapter's gradient within
    the room tests/test_remat.py gives tiny-llama (float32)."""
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
    assert len(jax.tree.leaves(grads[0])) == 2 * (8 * sizes["layers"] + 1)
    for path, g in jax.tree_util.tree_flatten_with_path(grads[0])[0]:
        want = grads[1]
        for k in path:
            want = want[k.key]
        np.testing.assert_allclose(np.asarray(g), np.asarray(want), rtol=0, atol=1e-6, err_msg=str(path))


def _one_layer(seeded, S, remat, flash=True):
    """One layer of the seeded model as a function of its input and its
    adapters: ``(fn, x, adapters)``; ``remat``: "none" (keeps everything),
    "plain" (``jax.checkpoint`` with no policy: keeps the input), "named"
    (the model's own policy)."""
    model, adapters, frozen, _ = seeded
    cfg = model.cfg
    layer = lm.LatentMoELayer(cfg)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, S, cfg.hidden_size)), cfg.dtype)
    mask = jnp.asarray(np.arange(S)[None] < np.array([S, S - 5])[:, None])
    key_bias = jnp.where(mask, 0.0, -1e30).astype(jnp.float32)
    bias = None if flash else lm.causal_bias(mask.astype(jnp.int32))
    la = lora.as_collection(adapters)["layer_0"]

    def fn(x, la):
        y, _ = layer.apply({"params": frozen["layer_0"], "lora": la}, x, bias, key_bias,
                           jnp.arange(S), mutable=["counters"])
        return y.astype(jnp.float32).sum()

    if remat == "plain":
        fn = jax.checkpoint(fn)
    elif remat == "named":
        fn = jax.checkpoint(fn, policy=jax.checkpoint_policies.save_only_these_names(*lm.REMAT_SAVED))
    return fn, x, la


def _kept_shapes(cfg, B, S, r, kernel):
    """``{(shape, dtype): how many}`` of the values ``REMAT_SAVED`` names in one layer."""
    H, D, dt = cfg.num_heads, cfg.qk_head_dim, jnp.dtype(cfg.dtype)
    kept = ([((B, S, r), jnp.dtype("float32"))] * 8  # every adapter's x a
            + [((B, S, cfg.q_lora_rank), dt), ((B, S, cfg.kv_lora_rank + cfg.qk_rope_head_dim), dt)]
            + [((B, H, S, D), dt)] * 3  # q, k, v
            + [((B, S, cfg.hidden_size), dt)]  # the residual stream after attention
            + [((B * S, cfg.n_routed_experts), jnp.dtype("float32")),
               ((B * S, cfg.num_experts_per_tok), jnp.dtype("int32"))]
            + [((B, S, cfg.moe_intermediate_size), dt)] * 2)  # the shared expert's gate and up
    if kernel:  # the flash kernel's output and one lane of its log-sum-exp
        kept += [((B, H, S, D), dt), ((B, H, S), jnp.dtype("float32"))]
    return collections.Counter(kept)


@pytest.mark.parametrize("path", ["dense", "kernel"])
def test_a_rematerialised_layer_keeps_the_named_set_and_no_more(path, seeded, monkeypatch):
    """``saved_residuals`` of one layer under the model's policy: beyond the
    layer's arguments and constants (weights, here closed over), exactly the
    values ``REMAT_SAVED`` names. A jitted function (``silu``, ``one_hot``)
    hands a kept value on under its own name: the same value twice in the
    list, once in memory."""
    from jax._src.ad_checkpoint import saved_residuals

    from bcfl_tpu.ops import registry

    kernel = path == "kernel"
    if kernel:  # the Pallas kernels, interpreted on the CPU
        monkeypatch.setattr(registry, "pallas_by_default", lambda: True)
    S = 128 if kernel else 16
    fn, x, la = _one_layer(seeded, S, "named", flash=kernel)
    cfg = seeded[0].cfg
    want = _kept_shapes(cfg, 2, S, 4, kernel)
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
    assert sum(want.values()) == len(lm.REMAT_SAVED) + 7 - (0 if kernel else 2)


def test_the_backward_pass_runs_no_product_and_no_kernel_of_the_forward_again(seeded, monkeypatch):
    """The jaxpr of one layer's gradient on the kernel's path: under the
    model's policy it has the matrix products and the Pallas calls (forward,
    dKV, dQ) of the layer that keeps everything, and no more; a
    ``jax.checkpoint`` with no policy has the forward's again (the test can
    see a retake)."""
    from bcfl_tpu.ops import registry

    monkeypatch.setattr(registry, "pallas_by_default", lambda: True)
    counts = {}
    for remat in ("none", "plain", "named"):
        fn, x, la = _one_layer(seeded, 128, remat)
        jaxpr = jax.make_jaxpr(jax.grad(fn, (0, 1)))(x, la).jaxpr
        counts[remat] = (str(jaxpr).count("pallas_call"), len(_products_of(jaxpr, [])))
    assert counts["none"][0] == 3 and counts["named"] == counts["none"], counts
    assert counts["plain"][0] == 4 and counts["plain"][1] > counts["none"][1], counts


def test_the_engine_records_what_the_layers_keep(tmp_path):
    """``FedEngine.remat_saved`` (on the ``run.start`` event too): the named
    values a layer keeps and their megabytes over the layers and a device's
    clients at a local step's shapes; zeros for a model with no save set."""
    from bcfl_tpu.fed.engine import FedEngine

    cell, sz = harness.load_cell(CELL, plumbing=True)
    run = harness.Run(cell, sz, SEED, 0.0, False, True, str(tmp_path), 0.0)
    engine = harness.setup_engine(run)
    cfg = engine.cfg
    assert cfg.remat and engine.model.cfg.remat
    got = engine.remat_saved
    # the CPU runs the XLA blockwise attention, which has no residuals of its own to name
    want = _kept_shapes(engine.model.cfg, cfg.batch_size, cfg.seq_len, cfg.lora_rank, kernel=False)
    assert got["remat_saved_values"] == sum(want.values()) == 18
    nbytes = sum(math.prod(shape) * dt.itemsize * n for (shape, dt), n in want.items())
    assert got["remat_saved_mb_per_step"] == pytest.approx(
        nbytes * sz["layers"] * engine.mesh.per_device / 1e6, abs=1e-3)
    assert got["remat_saved_mb_per_step"] > 0
    plain = FedEngine(FedConfig(name="plain", model="tiny-bert", dataset="synthetic", num_clients=2,
                                num_rounds=1, seq_len=16, batch_size=4, max_local_batches=1, remat=True))
    assert plain.model.cfg.remat and plain.remat_saved == {
        "remat_saved_values": 0, "remat_saved_mb_per_step": 0.0}


# --------------------------------------------------------------- the pieces

def test_the_shares_add_up():
    """The four shares' routed parts plus the shared expert once equal the
    uncut layer: nothing is dropped, nothing stands in for an absent chip."""
    cfg = get_config("tiny-latent-moe", dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(0), (2, 12, cfg.hidden_size)) * 0.5
    whole = experts.ExpertLayer(cfg)
    valid = jnp.ones(x.shape[:2], bool)
    params = whole.init(jax.random.key(1), x, valid)["params"]
    full = whole.apply({"params": params}, x, valid)
    shared = experts.SwiGLU(cfg, cfg.moe_intermediate_size).apply(
        {"params": params["shared_experts"]}, x)
    total = shared
    for s in range(4):
        held = (2 * s, 2 * s + 1)
        share = experts.ExpertLayer(get_config("tiny-latent-moe", dtype=jnp.float32, experts_held=held))
        p = dict(params, **{k: params[k][jnp.asarray(held)]
                            for k in ("experts_gate", "experts_up", "experts_down")})
        y, state = share.apply({"params": p}, x, valid, mutable=["counters"])
        total = total + (y - shared)
        c = state["counters"]
        assert float(c["moe_slots_held"] + c["moe_slots_absent"]) == 2 * 12 * 2
    np.testing.assert_allclose(total, full, atol=1e-5)
    assert float(jnp.abs(full - shared).max()) > 1e-3  # the routed part is there


def test_latent_attention_against_a_per_head_loop():
    cfg = get_config("tiny-latent-moe", dtype=jnp.float32, use_flash=False)
    B, S = 2, 10
    x = jax.random.normal(jax.random.key(2), (B, S, cfg.hidden_size))
    mask = jnp.asarray([[1] * 10, [1] * 7 + [0] * 3])
    att = lm.LatentAttention(cfg)
    args = (lm.causal_bias(mask), None, jnp.arange(S))
    params = att.init(jax.random.key(3), x, *args)["params"]
    got = np.asarray(att.apply({"params": params}, x, *args))

    P = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    dn, dr, dv, H = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.num_heads
    inv = np.asarray(lm.yarn_inv_freq(dr, cfg.rope_theta, cfg.rope_factor,
                                      cfg.rope_original_max_position, cfg.rope_beta_fast,
                                      cfg.rope_beta_slow), np.float64)

    def rms(v, g):
        return v / np.sqrt((v * v).mean(-1, keepdims=True) + cfg.rms_eps) * g

    def rot(v, pos):  # pairs (2i, 2i + 1)
        out = np.empty_like(v)
        for i in range(dr // 2):
            c, s = math.cos(pos * inv[i]), math.sin(pos * inv[i])
            out[2 * i] = v[2 * i] * c - v[2 * i + 1] * s
            out[2 * i + 1] = v[2 * i] * s + v[2 * i + 1] * c
        return out

    want = np.zeros((B, S, cfg.hidden_size))
    xs = np.asarray(x, np.float64)
    for b in range(B):
        cq = rms(xs[b] @ P["q_a_proj"]["kernel"], P["q_a_norm"]["scale"])
        q = (cq @ P["q_b_proj"]["kernel"]).reshape(S, H, dn + dr)
        ckv = xs[b] @ P["kv_a_proj"]["kernel"]
        kv = (rms(ckv[:, :cfg.kv_lora_rank], P["kv_a_norm"]["scale"])
              @ P["kv_b_proj"]["kernel"]).reshape(S, H, dn + dv)
        k_rope = np.stack([rot(ckv[t, cfg.kv_lora_rank:], t) for t in range(S)])
        heads = []
        for h in range(H):
            out = np.zeros((S, dv))
            for t in range(S):
                qt = np.concatenate([q[t, h, :dn], rot(q[t, h, dn:], t)])
                keys = [u for u in range(t + 1) if mask[b, u]]
                sc = np.array([qt @ np.concatenate([kv[u, h, :dn], k_rope[u]]) for u in keys])
                sc = sc * lm.softmax_scale(cfg)
                w = np.exp(sc - sc.max())
                w /= w.sum()
                out[t] = sum(wi * kv[u, h, dn:] for wi, u in zip(w, keys))
            heads.append(out)
        want[b] = np.concatenate(heads, -1) @ P["o_proj"]["kernel"]
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_yarn_frequencies_and_softmax_scale_by_hand():
    """Published sizes: 64 rotary dimensions, theta 10000, factor 128 over an
    original context of 8192, beta 32 and 1. The correction dimensions are
    32 ln(8192 / (32 * 2 pi)) / ln 10000 = 12.88 and 32 ln(8192 / (2 pi)) /
    ln 10000 = 24.92: pairs 0..12 keep their frequency, 25.. are divided by
    128, a linear ramp over 12..25 between."""
    cfg = get_config("mistral-small-4")
    f = np.asarray(lm.yarn_inv_freq(64, 10000.0, 128.0, 8192, 32.0, 1.0), np.float64)
    plain_f = 10000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(f[:13], plain_f[:13], rtol=1e-6)
    np.testing.assert_allclose(f[25:], plain_f[25:] / 128.0, rtol=1e-6)
    ramp = (18 - 12) / 13.0
    np.testing.assert_allclose(f[18], plain_f[18] / 128 * ramp + plain_f[18] * (1 - ramp), rtol=1e-6)
    np.testing.assert_allclose(f, plain.yarn_inv_freq(64, 10000.0, 128.0, 8192, 32.0, 1.0), rtol=1e-6)
    assert abs(lm.softmax_scale(cfg) - 128 ** -0.5 * (0.1 * math.log(128) + 1) ** 2) < 1e-12
    assert abs(lm.softmax_scale(cfg) - 0.1949695) < 1e-6
    sz = json.load(open(os.path.join(harness.HERE, "configs", "mistral-small-4.json")))
    assert abs(plain.softmax_scale(sz) - lm.softmax_scale(cfg)) < 1e-12


def test_adapters_on_the_activations_equal_the_merged_form():
    dense = experts.LoRADense(24, jnp.float32, jnp.float32)
    x = jax.random.normal(jax.random.key(4), (5, 16))
    w = dense.init(jax.random.key(5), x)["params"]
    a = jax.random.normal(jax.random.key(6), (16, 4)) * 0.3
    b = jax.random.normal(jax.random.key(7), (4, 24)) * 0.3
    got = dense.apply({"params": w, "lora": {"a": a, "b": b}}, x)
    np.testing.assert_allclose(got, x @ (w["kernel"] + a @ b), atol=1e-5)
    np.testing.assert_allclose(dense.apply({"params": w}, x), x @ w["kernel"], atol=1e-6)
    # the policy: float32 adapters, applied on the activations, no merged kernel
    model = build("tiny-latent-moe", head="lm", param_dtype=jnp.bfloat16)
    pol = lora_policy(model)
    assert pol.on_activations and pol.adapter_dtype == "float32" and not pol.head_modules
    assert "router" not in pol.targets and not lora_policy("tiny-llama").on_activations
    ids = jnp.ones((1, 8), jnp.int32)
    params = jax.jit(model.init)(jax.random.key(0), ids, ids)["params"]
    ad = lora.init_lora(jax.random.key(1), params, 4, targets=pol.targets,
                        head_modules=pol.head_modules, dtype=pol.adapter_dtype)
    assert {str(v.dtype) for v in jax.tree.leaves(ad)} == {"float32"}
    assert len(ad) == 8 * 2 + 1 and not any("router" in k or "experts_" in k for k in ad)
    v = model_variables(model, ad, params)
    assert v["params"] is params and set(v["lora"]) == {"layer_0", "layer_1", "lm_head"}


def _block_inputs(C=3, N=10, k=2, H=128, F=128, G=4, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(ks[0], (C, N, H)) * 0.5
    slot = jax.random.randint(ks[1], (C, N, k), 0, G + 3).clip(0, G)  # G = absent
    cw = jax.nn.softmax(jax.random.normal(ks[2], (C, N, k)), -1)
    wg, wu = (jax.random.normal(ks[i], (G, H, F)) * 0.1 for i in (3, 4))
    wd = jax.random.normal(ks[5], (G, F, H)) * 0.1
    return x, slot, cw, wg, wu, wd


def _block_plain(x, slot, cw, wg, wu, wd):
    """One client's rows by a loop over the held experts with a mask."""
    y = jnp.zeros_like(x)
    for e in range(wg.shape[0]):
        w_e = jnp.where(slot == e, cw, 0.0).sum(-1)
        y = y + w_e[:, None] * ((jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e])
    return y


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_folded_grouped_product_equals_a_per_client_loop(impl, monkeypatch):
    """Under ``vmap`` with the expert weights NOT batched the clients fold
    into the rows (one grouped product over C x N x k rows), forward and
    backward, whichever implementation serves the product."""
    monkeypatch.setattr(experts, "grouped_matmul",
                        lambda *a, **kw: grouped_matmul(*a, impl=impl, **kw))
    x, slot, cw, wg, wu, wd = _block_inputs()
    folded = jax.vmap(experts.expert_block, in_axes=(0, 0, 0, None, None, None))
    got = folded(x, slot, cw, wg, wu, wd)
    want = jnp.stack([_block_plain(x[c], slot[c], cw[c], wg, wu, wd) for c in range(3)])
    np.testing.assert_allclose(got, want, atol=2e-4)
    t = jax.random.normal(jax.random.key(9), got.shape)
    gx, gcw = jax.grad(lambda x_, cw_: (folded(x_, slot, cw_, wg, wu, wd) * t).sum(), (0, 1))(x, cw)
    wx, wcw = jax.grad(lambda x_, cw_: (jnp.stack([
        _block_plain(x_[c], slot[c], cw_[c], wg, wu, wd) for c in range(3)]) * t).sum(), (0, 1))(x, cw)
    np.testing.assert_allclose(gx, wx, atol=5e-4)
    np.testing.assert_allclose(gcw, wcw, atol=5e-4)
    # one row axis gives the same as the fold
    np.testing.assert_allclose(experts.expert_block(x[1], slot[1], cw[1], wg, wu, wd), want[1], atol=2e-4)


def test_batched_expert_weights_are_refused_under_vmap():
    x, slot, cw, wg, wu, wd = _block_inputs()
    stack = lambda w: jnp.broadcast_to(w, (3,) + w.shape)  # noqa: E731
    with pytest.raises(NotImplementedError, match="not batched"):
        jax.vmap(experts.expert_block)(x, slot, cw, stack(wg), stack(wu), stack(wd))


@pytest.mark.parametrize("transpose_rhs", [False, True])
def test_grouped_matmul_kernel_against_its_reference(transpose_rhs):
    """The registry's two implementations agree, rows of no group come out
    zero, and a row count that is no multiple of the tile is taken."""
    ks = jax.random.split(jax.random.key(11), 2)
    M, K, N, G = 200, 128, 256, 5
    lhs = jax.random.normal(ks[0], (M, K))
    rhs = jax.random.normal(ks[1], (G, N, K) if transpose_rhs else (G, K, N)) * 0.1
    sizes_ = jnp.asarray([30, 0, 70, 1, 40], jnp.int32)  # 141 of 200 rows live
    a = grouped_matmul(lhs, rhs, sizes_, transpose_rhs, impl="xla")
    b = grouped_matmul(lhs, rhs, sizes_, transpose_rhs, impl="pallas")
    w = jnp.swapaxes(rhs, 1, 2) if transpose_rhs else rhs
    want = np.zeros((M, N), np.float32)
    start = 0
    for g, n in enumerate(np.asarray(sizes_)):
        want[start:start + n] = np.asarray(lhs[start:start + n] @ w[g])
        start += n
    np.testing.assert_allclose(a, want, atol=1e-4)
    np.testing.assert_allclose(b, want, atol=1e-4)
    assert float(jnp.abs(b[141:]).max()) == 0.0 == float(jnp.abs(a[141:]).max())


def _products_of(jaxpr, out):
    """``[(primitive, output shape, FLOP), ...]`` of every matrix product in
    a jaxpr, the sub-jaxprs of its equations with it."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in ("dot_general", "ragged_dot_general", "ragged_dot"):
            a, b = eqn.invars[0].aval.shape, eqn.invars[1].aval.shape
            dn = eqn.params.get("dimension_numbers") or eqn.params["ragged_dot_dimension_numbers"].dot_dimension_numbers
            contract = math.prod(a[d] for d in dn[0][0])
            rows = math.prod(eqn.outvars[0].aval.shape)
            out.append((name, tuple(eqn.outvars[0].aval.shape), 2.0 * rows * contract, b))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _products_of(sub, out)
    return out


def test_the_lowered_step_has_no_weight_gradient_of_a_frozen_kernel(sizes, seeded):
    """Every matrix product of one client's gradient step (tiny preset, dense
    attention, no recomputation), read from its jaxpr: none has a frozen
    kernel's shape as its result (the product LoRA exists to avoid), and
    their operations lie within 10% of the family's required count once that
    count is moved to what this lowering computes by design: every key of a
    row (dense attention skips no causal half); one chunk of the sorted
    assignments (a count by shapes sees the loop's body once, and all of a
    chunk's rows, a quarter of all assignments, where the kernel computes
    the held ones); the gate and up products once more in the backward pass
    (recomputation, which the required count leaves out)."""
    _, adapters, frozen, _ = seeded
    p = fam.program(sizes)
    model = build(p["model"], head="lm", vocab_size=p["vocab_size"], dtype=jnp.float32,
                  param_dtype=jnp.float32, remat=False)
    S, B = 24, 3
    loss_fn = make_loss_fn(model, "causal_lm")
    b = {"ids": jnp.ones((B, S), jnp.int32), "mask": jnp.ones((B, S), jnp.int32),
         "example_mask": jnp.ones((B,))}
    jaxpr = jax.make_jaxpr(jax.grad(lambda t: loss_fn(t, frozen, b, None)[0]))(adapters)
    found = _products_of(jaxpr.jaxpr, [])
    kernels = {tuple(x.shape) for x in jax.tree.leaves(frozen) if x.ndim >= 2}
    assert len(found) > 50 and any(n.startswith("ragged") for n, *_ in found)
    assert not [f for f in found if f[1] in kernels], "a weight-gradient product of a frozen kernel"
    assert not [f for f in found if len(f[1]) == 3 and f[1][1:] in kernels]  # nor a stack of them
    d = weights.dims(sizes)
    tokens = B * S
    by = flops.by_group(sizes, S)
    attn_act = 3 * d["L"] * 2 * d["heads"] * (d["dn"] + d["dr"] + d["dv"])  # x keys a query
    expected = (sum(req for _, req in by.values())
                + attn_act * (S - (S + 1) / 2.0)
                + by["routed experts"][1]
                * (d["E"] / d["G"] / experts.CHUNK_SHARE * (8.0 / 6.0) - 1.0)) * tokens
    got = sum(f[2] for f in found)
    assert abs(got - expected) / expected < 0.10, (got, expected)
    merged = sum(f for _, f, trained in flops.products(sizes, S) if not trained) * tokens
    assert got < expected + 0.5 * merged  # what weight gradients of the base would add


def test_the_required_operations_by_the_rule():
    """By hand at the published sizes, 8 layers, 16 of 128 experts, 16384
    rows, rank 16, sequence 2048 (PERF.md section 4 shows the sum)."""
    sz = json.load(open(os.path.join(harness.HERE, "configs", "mistral-small-4.json")))
    attn = 4096 * 1024 + 1024 * 4096 + 4096 * 320 + 256 * 6144 + 4096 * 4096
    shared, router, expert = 3 * 4096 * 2048, 4096 * 128, 3 * 4096 * 2048
    frozen = 8 * 2 * (attn + shared + router + expert * 4 * 16 / 128) + 2 * 4096 * 16384
    adapters = 8 * 2 * 16 * ((4096 + 1024) + (1024 + 4096) + (4096 + 320) + (256 + 6144)
                             + (4096 + 4096) + 2 * (4096 + 2048) + (2048 + 4096)) \
        + 2 * 16 * (4096 + 16384)
    attention = 8 * 2 * 1024.5 * 32 * (128 + 128)
    assert flops.forward_flops_per_token(sz, 2048) == frozen + adapters + attention
    assert flops.train_flops_per_token(sz, 2048) == 2 * frozen + 3 * (adapters + attention)
    assert 2.8e9 < flops.train_flops_per_token(sz, 2048) < 2.9e9
    assert fam.program(sz) == {
        "model": "mistral-small-4@layers=8,experts_held=16", "vocab_size": 16384, "num_labels": 2,
        "task": "causal_lm", "lora_rank": 16, "remat": True, "use_flash": True}
    work = flops.grouped_matmul_work(sz, 1000, 3)
    assert work == (6 * 2.0 * 1000 * 4096 * 2048,
                    6 * 3 * 16 * 4096 * 2048 * 2 + 6 * 1000 * (4096 + 2048) * 2)


def test_the_counters_sum_over_steps_layers_and_clients(sizes, seeded):
    model, adapters, frozen, _ = seeded
    loss_fn = make_loss_fn(model, "causal_lm")
    assert loss_fn.counters == experts.COUNTERS == model.COUNTERS
    assert make_loss_fn(build("tiny-llama", head="lm"), "causal_lm").counters == ()
    lt = make_local_train(make_optimizer("adamw", 1e-3), loss_fn)
    C, T, B, S = 2, 3, 2, 16
    rng = np.random.default_rng(1)
    batches = {"ids": jnp.asarray(rng.integers(4, 512, (C, T, B, S)), jnp.int32),
               "mask": jnp.ones((C, T, B, S), jnp.int32), "example_mask": jnp.ones((C, T, B))}
    _, stats = jax.jit(jax.vmap(lt, in_axes=(None, None, 0, 0)))(
        adapters, frozen, batches, jax.random.split(jax.random.key(0), C))
    assert stats.shape == (C, 6)
    held, absent, rows_max = np.asarray(stats[:, 3:]).T
    np.testing.assert_array_equal(held + absent, T * sizes["layers"] * B * S * 2)
    assert (held > 0).all() and (absent > 0).all()
    # the largest over steps and layers of one client's fullest expert
    one_step = [float(make_loss_fn(model, "causal_lm")(
        adapters, frozen, jax.tree.map(lambda x: x[0, j], batches), None)[1][4]) for j in range(T)]
    assert rows_max[0] == max(one_step) and rows_max[0] <= B * S


# ------------------------------------------------------- registry and refusals

def test_three_families_by_name_and_by_model():
    assert family_of("tiny-bert") == "encoder" and family_of("tiny-llama") == "llama"
    assert family_of("mistral-small-4@layers=8,experts_held=16") == "latent_moe"
    assert family_of(build("tiny-latent-moe", head="lm")) == "latent_moe"
    assert family_of(build("tiny-llama", head="lm")) == "llama"
    cfg = get_config("mistral-small-4@layers=8,experts_held=16", vocab_size=16384)
    assert (cfg.num_layers, cfg.held, cfg.vocab_size) == (8, tuple(range(16)), 16384)
    assert get_config("tiny-latent-moe", experts_held=(2, 5)).held == (2, 5)
    assert get_config("mistral-small-4").held == tuple(range(128))
    assert (get_config("mistral-small-4").num_layers, get_config("mistral-small-4").vocab_size) == (36, 131072)
    with pytest.raises(KeyError, match="tiny-latent-moe"):
        family_of("no-such-model")
    with pytest.raises(KeyError, match="experts_held"):
        get_config("tiny-latent-moe@vocab_rows=256")
    with pytest.raises(ValueError, match="not a set of experts"):
        get_config("tiny-latent-moe@experts_held=9").held
    with pytest.raises(ValueError, match="LM head only"):
        build("tiny-latent-moe")
    with pytest.raises(NotImplementedError, match="encoder and llama"):
        tp_param_specs(build("tiny-latent-moe", head="lm"), {})
    with pytest.raises(TypeError, match="no family"):
        family_of(object())


@pytest.mark.parametrize("fields,why", [
    (dict(task="classification"), "LM head only"),
    (dict(lora_rank=0), "no weight-gradient pass"),
    (dict(tp=2, num_clients=2), "no tensor-parallel layout"),
    (dict(sp=2, num_clients=2), "ring attention is not wired"),
    (dict(lora_ranks="2,4", lora_rank=0, num_clients=2), "heterogeneous"),
])
def test_config_time_refusals(fields, why):
    base = dict(model="tiny-latent-moe", task="causal_lm", lora_rank=4, vocab_size=512)
    FedConfig(**base)  # the pairing that runs
    with pytest.raises(ValueError, match=why):
        FedConfig(**{**base, **fields})
