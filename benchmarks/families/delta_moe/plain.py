"""The plain reference of the Gated DeltaNet and gated attention decoder with
experts under LoRA (``qwen3_next``): forward, loss, gradients, AdamW and the
example-weighted mean, in ``jax.numpy``, float32 at ``highest`` (or a named
precision, :func:`ops`), importing nothing of the program and nothing of another family.

``x0 = emb[ids]``; a layer is ``h = x + Mix(zrms(x))``, ``y = h + MoE(n)``,
``n = zrms(h)``; ``logits = zrms(x_L) head + adapter``, the head untied.
``zrms`` is the zero-centred RMSNorm ``x rsqrt(mean x^2 + eps) (1 + w)``.
``Mix`` is Gated DeltaNet or gated attention, full attention every
``full_attention_interval``-th layer.

- The rule runs A POSITION AT A TIME, the definition: ``S <- exp(g_t) S``;
  ``S <- S + k_t (beta_t (v_t - S^T k_t))^T``; ``o_t = S^T q_t``, a
  ``lax.scan`` over the positions of a row, in blocks of ``gdn_chunk_size``
  positions under ``jax.checkpoint`` so that the backward pass holds one
  block's states. No chunked form, no triangular solve, no cumulative sums.
  Value head ``j`` reads key head ``j // (Hv / Hk)``.
- Attention: a masked softmax a head (a ``lax.map`` over the query heads, so
  one head's [S, S] scores stand at once), key-value head ``h // (heads /
  kv_heads)``, scale ``head_dim^-0.5``, rotary in the rotate-half form on the
  first ``partial_rotary_factor`` of each head, the output times
  ``sigmoid(gate)``.
- The router in the published order: softmax over all experts in float32,
  the top k, renormalised (``norm_topk_prob``). The held experts are a plain
  loop with a mask (a scan over the held stack, every row through every held
  expert, weighed by its combine weight or zero). What the absent experts
  would add is left out, as in the program, and a padded position is routed
  to no expert. The shared expert is times ``sigmoid(u sgate)``.
- The model never stands whole on the device: a client's step runs forward
  through the layers keeping each layer's input, and backward a layer at a
  time by ``jax.vjp`` with that layer's weights drawn again from the seed.

Departures from the published model, as the configuration's file says: no
multi-token-prediction module, no router balance term. In a precision below
float32 (``ops``) the matrix products round their operands and the pipeline
its activations; inside the rule the state stays float32 and is rounded as
an operand where it meets ``k`` and ``q``.
"""

from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import weights

HI = lax.Precision.HIGHEST


def _bf16(x):
    return x + lax.stop_gradient(lax.reduce_precision(x, 8, 7) - x)


def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 240.0
    return x + lax.stop_gradient(lax.reduce_precision(x / s, 4, 3) * s - x)


def ops(precision):
    """``(operand, act)``: how a matrix unit sees an operand and how the
    pipeline holds an activation. "f32": as they are, at ``highest``; "bf16":
    both rounded to bfloat16 (what the configuration states; the adapters
    stay float32 in store and are rounded as operands); "fp8": operands to an
    8-bit float inside the bfloat16 pipeline."""
    ident = lambda x: x  # noqa: E731
    return {"f32": (ident, ident), "bf16": (_bf16, _bf16), "fp8": (_fp8, _bf16)}[precision]


def product_precision(precision):
    """The matrix unit's precision for products of ``ops``' operands:
    ``highest`` but where every operand is a bfloat16 value already ("bf16"),
    whose products one bfloat16 pass computes exactly, adding in float32
    (about a sixth of ``highest``'s passes on a TPU)."""
    return lax.Precision.DEFAULT if precision == "bf16" else HI


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _zrms(x, w, eps):
    return _rms(x, 1.0 + w, eps)


def _l2(x, eps):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _f32(x):
    return x.astype(jnp.float32)


Static = collections.namedtuple("Static", (
    "dims", "held", "kind", "eps", "theta", "precision", "drop_expert", "no_carry", "no_erase",
    "no_gate"))


def _static(sizes, kind, precision, drop_expert=None, no_carry=False, no_erase=False,
            no_gate=False):
    """What a jitted piece needs of the configuration, hashable."""
    d = weights.dims(sizes)
    return Static(tuple(sorted((k, v) for k, v in d.items() if k not in ("held", "kinds"))),
                  d["held"], kind, sizes["rms_norm_eps"], float(sizes["rope_theta"]), precision,
                  drop_expert, no_carry, no_erase, no_gate)


# ------------------------------------------------------------- the mixers

def rule(q, k, v, g, beta, block, act, no_carry=False, no_erase=False):
    """The gated delta rule, a position at a time: ``q``/``k`` [B, S, Hk, Dk],
    ``v`` [B, S, Hv, Dv], ``g``/``beta`` [B, S, Hv] -> ``o`` as ``v``.
    ``no_carry`` plants a fault: the state is NOT carried from one block of
    ``block`` positions to the next (every block starts from zero);
    ``no_erase`` another: the rule without its ``- S^T k_t`` term (plain
    gated linear attention)."""
    S, Hv = v.shape[1], v.shape[2]
    r = Hv // k.shape[2]
    pad = -S % block

    def row(q, k, v, g, beta):
        if pad:  # g 0 and beta 0: nothing decays and nothing is written
            q, k, v, g, beta = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                                for a in (q, k, v, g, beta))
        cut = lambda a: a.reshape((-1, block) + a.shape[1:])  # noqa: E731

        def step(state, t):
            qt, kt, vt, gt, bt = t
            qt, kt = jnp.repeat(qt, r, 0), jnp.repeat(kt, r, 0)  # [Hv, Dk]
            state = jnp.exp(gt)[:, None, None] * state
            read = 0.0 if no_erase else (act(state) * kt[:, :, None]).sum(1)
            u = act(bt[:, None] * (vt - read))
            state = state + kt[:, :, None] * u[:, None, :]
            return state, (act(state) * qt[:, :, None]).sum(1)

        @jax.checkpoint  # the backward pass holds one block's states
        def a_block(state, ts):
            if no_carry:
                state = jnp.zeros_like(state)
            # unrolled eight positions a loop iteration: the same sequential
            # updates, fewer trips through the device's loop machinery
            return lax.scan(step, state, ts, unroll=8)

        state0 = jnp.zeros((Hv, k.shape[-1], v.shape[-1]), jnp.float32)
        _, o = lax.scan(a_block, state0, tuple(cut(a) for a in (q, k, v, g, beta)))
        return o.reshape((-1,) + o.shape[2:])[:S]

    return jax.vmap(row)(q, k, v, g, beta)


def gdn_mix(h, w, lin, frozen, d, eps, act, static):
    B, S, _ = h.shape
    Hk, Hv, Dk, Dv, K = d["Hk"], d["Hv"], d["Dk"], d["Dv"], d["K"]
    r = Hv // Hk
    # a key head at a time: q, k, then its value heads' v and z; b, then a
    qkvz = lin(h, "qkvz").reshape(B, S, Hk, 2 * Dk + 2 * r * Dv)
    q, k = qkvz[..., :Dk], qkvz[..., Dk:2 * Dk]
    v = qkvz[..., 2 * Dk:2 * Dk + r * Dv].reshape(B, S, Hv * Dv)
    z = qkvz[..., 2 * Dk + r * Dv:].reshape(B, S, Hv, Dv)
    ba = frozen(h, "ba").reshape(B, S, Hk, 2 * r)
    b, a = ba[..., :r].reshape(B, S, Hv), ba[..., r:].reshape(B, S, Hv)
    mixed = jnp.concatenate([q.reshape(B, S, Hk * Dk), k.reshape(B, S, Hk * Dk), v], -1)
    # depthwise, causal, no bias: conv[j] weighs the position K - 1 - j back
    padded = jnp.pad(mixed, ((0, 0), (K - 1, 0), (0, 0)))
    conv = _f32(w["conv"])
    mixed = act(jax.nn.silu(sum(conv[j] * padded[:, j:j + S] for j in range(K))))
    q = act(_l2(mixed[..., :Hk * Dk].reshape(B, S, Hk, Dk), eps) / np.sqrt(Dk))
    k = act(_l2(mixed[..., Hk * Dk:2 * Hk * Dk].reshape(B, S, Hk, Dk), eps))
    v = mixed[..., 2 * Hk * Dk:].reshape(B, S, Hv, Dv)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(_f32(w["alog"])) * jax.nn.softplus(a + _f32(w["dtb"]))
    o = act(rule(q, k, v, g, beta, d["chunk"], act, static.no_carry, static.no_erase))
    gated = act(act(_rms(o, _f32(w["gn"]), eps)) * jax.nn.silu(z))
    return lin(gated.reshape(B, S, Hv * Dv), "out")


def rope(x, theta, rot):
    """Rotate-half rotary on the first ``rot`` of the last axis of ``x`` [B, S,
    heads, D] at positions 0 .. S-1."""
    S, half = x.shape[1], rot // 2
    freqs = 1.0 / (theta ** (np.arange(0, rot, 2, dtype=np.float64) / rot))
    ang = np.arange(S, dtype=np.float64)[:, None] * freqs[None, :]
    ang = np.concatenate([ang, ang], -1)
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    xr = x[..., :rot]
    turned = jnp.concatenate([-xr[..., half:], xr[..., :half]], -1)
    return jnp.concatenate([xr * cos + turned * sin, x[..., rot:]], -1)


def attention_mix(h, w, mask, lin, d, eps, theta, op, act, no_gate, prec):
    B, S, _ = h.shape
    heads, kv, hd = d["heads"], d["kv"], d["hd"]
    qg = lin(h, "q").reshape(B, S, heads, 2 * hd)
    gate = qg[..., hd:]
    q = act(rope(act(_zrms(qg[..., :hd], _f32(w["qn"]), eps)), theta, d["rot"]))
    k = act(rope(act(_zrms(lin(h, "k").reshape(B, S, kv, hd), _f32(w["kn"]), eps)), theta, d["rot"]))
    v = lin(h, "v").reshape(B, S, kv, hd)
    ok = jnp.tril(jnp.ones((S, S), bool))[None] & (mask[:, None, :] > 0)
    bias = jnp.where(ok, 0.0, -1e30)  # [B, S, S]

    @jax.checkpoint
    def a_head(i):
        qi = q[:, :, i]
        ki, vi = (lax.dynamic_index_in_dim(a, i // (heads // kv), 2, keepdims=False)
                  for a in (k, v))
        s = jnp.einsum("bqd,bkd->bqk", op(qi), op(ki), precision=prec) / np.sqrt(hd) + bias
        s = s - s.max(-1, keepdims=True)
        e = jnp.exp(s)
        p = e / (e.sum(-1, keepdims=True) + 1e-9)
        return jnp.einsum("bqk,bkd->bqd", op(act(p)), op(vi), precision=prec)

    a = act(lax.map(a_head, jnp.arange(heads)).transpose(1, 2, 0, 3))  # [B, S, heads, hd]
    if not no_gate:
        a = act(a * jax.nn.sigmoid(gate))
    return lin(a.reshape(B, S, heads * hd), "o")


# --------------------------------------------------------------- a layer

def layer_fwd(x, w, ad, mask, static):
    """One layer: ``x`` [B, S, H] -> ``y``; ``ad`` holds the layer's adapters
    under their short names (``qkvz.a`` ...)."""
    held, eps, drop = static.held, static.eps, static.drop_expert
    d = dict(static.dims)
    op, act = ops(static.precision)
    prec = product_precision(static.precision)
    B, S, H = x.shape

    def mm(a, b):
        return jnp.matmul(op(a), op(b), precision=prec)

    def lin(h, name):
        xa = act(mm(h, ad[name + ".a"]))
        return act(act(mm(h, _f32(w[name]))) + act(mm(xa, ad[name + ".b"])))

    def frozen(h, name):
        return act(mm(h, _f32(w[name])))

    h = act(_zrms(x, _f32(w["n1"]), eps))
    if static.kind == weights.LINEAR:
        mixed = gdn_mix(h, w, lin, frozen, d, eps, act, static)
    else:
        mixed = attention_mix(h, w, mask, lin, d, eps, static.theta, op, act, static.no_gate, prec)
    x = act(x + act(mixed))

    # ---- expert layer
    h = act(_zrms(x, _f32(w["n2"]), eps))
    shared = lin(act(jax.nn.silu(lin(h, "sg")) * lin(h, "su")), "sd")
    shared = act(shared * jax.nn.sigmoid(frozen(h, "sgate")))
    rows = h.reshape(B * S, H)
    probs = jax.nn.softmax(jnp.matmul(rows, _f32(w["r"]), precision=HI), axis=-1)
    top_p, top_i = lax.top_k(probs, d["k"])
    cw = top_p / top_p.sum(-1, keepdims=True)
    real = mask.reshape(B * S, 1) > 0

    @jax.checkpoint  # the backward pass keeps an expert's inputs and computes it again
    def expert(out, xs):
        e_id, eg, eu, ed = xs
        # [T]: zero where not assigned, and at a padded position
        w_e = jnp.where((top_i == e_id) & real, cw, 0.0).sum(-1)
        if drop is not None:
            w_e = jnp.where(e_id == drop, 0.0, w_e)
        a_ = act(jax.nn.silu(act(mm(rows, _f32(eg)))) * act(mm(rows, _f32(eu))))
        return out + w_e[:, None] * act(mm(a_, _f32(ed))), None

    routed, _ = lax.scan(expert, jnp.zeros((B * S, H), jnp.float32),
                         (jnp.asarray(held, jnp.int32), w["eg"], w["eu"], w["ed"]))
    return act(x + act(shared + act(routed).reshape(B, S, H)))


def head_logits(x, we, ad, static):
    """Final norm and the untied head: float32 logits [B, S, V]."""
    op, act = ops(static.precision)
    prec = product_precision(static.precision)
    x = act(_zrms(x, _f32(we["norm"]), static.eps))
    xa = act(jnp.matmul(op(x), op(ad["lm.a"]), precision=prec))
    return (jnp.matmul(op(x), op(_f32(we["head"])), precision=prec)
            + jnp.matmul(op(xa), op(ad["lm.b"]), precision=prec))


def head_loss(x, we, ad, batch, static):
    """Next-token cross-entropy over the head's logits: ``(loss, (correct,
    tokens))``, a target counted where it is a real token of a real
    example."""
    logits = head_logits(x, we, ad, static)[:, :-1]
    targets = batch["ids"][:, 1:]
    wt = _f32(batch["mask"][:, 1:]) * _f32(batch["example_mask"])[:, None]
    if "target_mask" in batch:
        wt = wt * batch["target_mask"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    per_tok = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    n = jnp.maximum(wt.sum(), 1.0)
    correct = ((jnp.argmax(logits, -1) == targets) * wt).sum()
    return (per_tok * wt).sum() / n, (correct, wt.sum())


_fwd = jax.jit(layer_fwd, static_argnames=("static",))


@functools.partial(jax.jit, static_argnames=("static",))
def _bwd(x, w, ad, mask, dy, static):
    _, vjp = jax.vjp(lambda x_, ad_: layer_fwd(x_, w, ad_, mask, static), x, ad)
    return vjp(dy)


@functools.partial(jax.jit, static_argnames=("static",))
def _head(x, we, ad, batch, static):
    (loss, aux), (dx, dad) = jax.value_and_grad(head_loss, argnums=(0, 2), has_aux=True)(
        x, we, ad, batch, static)
    return loss, aux, dx, dad


@functools.partial(jax.jit, static_argnames=("act",))
def _embed(emb, ids, act):
    return ops(act)[1](_f32(emb)[ids])


def _of_layer(trained, i):
    n = f"L{i}."
    return {k[len(n):]: v for k, v in trained.items() if k.startswith(n)}


def _forward(trained, sizes, seed, batch, precision, keep, **fault):
    """Through the layers; ``keep``: every layer's input, for the backward
    pass. Returns ``(xs, statics, ends)``."""
    statics = [_static(sizes, kind, precision, **fault) for kind in weights.kinds(sizes)]
    we = weights.ends(sizes, seed)
    xs = [_embed(we["emb"], batch["ids"], "f32" if precision == "f32" else "bf16")]
    for i, static in enumerate(statics):
        y = _fwd(xs[-1], weights.layer(sizes, seed, i), _of_layer(trained, i), batch["mask"], static)
        xs = xs + [y] if keep else [y]
    return xs, statics, we


def logits(trained, sizes, seed, batch, precision="f32"):
    """The forward pass alone: float32 logits [B, S, V]."""
    xs, statics, we = _forward(trained, sizes, seed, batch, precision, keep=False)
    return head_logits(xs[-1], we, {k: trained[k] for k in ("lm.a", "lm.b")}, statics[-1])


def loss_and_grad(trained, sizes, seed, batch, precision="f32", **fault):
    """``(loss, tokens, grads)`` of one client's batch: ``grads`` in the
    flat naming of ``trained``."""
    xs, statics, we = _forward(trained, sizes, seed, batch, precision, keep=True, **fault)
    head_ad = {k: trained[k] for k in ("lm.a", "lm.b")}
    loss, (_, n), dx, grads = _head(xs[-1], we, head_ad, batch, statics[-1])
    grads = dict(grads)
    for i in reversed(range(len(statics))):
        dx, dad = _bwd(xs[i], weights.layer(sizes, seed, i), _of_layer(trained, i),
                       batch["mask"], dx, statics[i])
        grads.update({f"L{i}.{k}": v for k, v in dad.items()})
        xs.pop()
    return loss, n, grads


@jax.jit
def _adamw(p, mu, nu, t, g, hp):
    t = t + 1
    mu = jax.tree.map(lambda m, x: hp["b1"] * m + (1 - hp["b1"]) * x, mu, g)
    nu = jax.tree.map(lambda v, x: hp["b2"] * v + (1 - hp["b2"]) * x * x, nu, g)
    c1, c2 = 1 - hp["b1"] ** t, 1 - hp["b2"] ** t
    p = jax.tree.map(
        lambda p_, m, v: p_ - hp["lr"] * ((m / c1) / (jnp.sqrt(v / c2) + hp["eps"]) + hp["wd"] * p_),
        p, mu, nu)
    return p, mu, nu, t, jax.tree.map(lambda x: jnp.sqrt(jnp.sum(x * x)), g)


def run_rounds(sizes, seed, batches, masks, n_ex, precision="f32", half_batch=False,
               drop_client=None, **fault):
    """The first ``len(masks)`` rounds: every client's local AdamW steps over
    the adapters from the round's global ones with a fresh state, then the
    example-weighted mean under the round's mask; one client and one step at
    a time. The adapters stay float32 throughout. The planted faults:
    ``half_batch`` (half of a batch's rows feed the loss; of a batch of one
    row, the first half of its targets), ``drop_client``, and this family's
    own (``fault``): ``drop_expert`` (held expert e's part left out),
    ``no_carry`` and ``no_erase`` (:func:`rule`) and ``no_gate`` (the
    attention's output gate left out)."""
    tr = sizes["training"]
    hp = {k: jnp.float32(tr[v]) for k, v in (("lr", "learning_rate"), ("b1", "b1"), ("b2", "b2"),
                                             ("eps", "eps"), ("wd", "weight_decay"))}
    start = weights.adapters(sizes, seed)
    batches = jax.tree.map(jnp.asarray, batches)
    C, steps = batches["ids"].shape[:2]
    g, losses, gnorm0, step_losses = start, [], None, []
    for r, mask in enumerate(masks):
        new, tot = [], np.zeros(2)
        for c in range(C):
            p = g
            mu = jax.tree.map(jnp.zeros_like, p)
            nu, t = mu, jnp.float32(0)
            for j in range(steps):
                b = {k: v[c, j] for k, v in batches.items()}
                if half_batch:
                    B, S = b["ids"].shape
                    if B > 1:
                        b = dict(b, example_mask=b["example_mask"] * (jnp.arange(B) < B // 2))
                    else:  # one row a batch: the first half of its targets
                        b = dict(b, target_mask=_f32(jnp.arange(S - 1) < (S - 1) // 2)[None])
                loss, n, grads = loss_and_grad(p, sizes, seed, b, precision, **fault)
                p, mu, nu, t, gn = _adamw(p, mu, nu, t, grads, hp)
                tot += np.array([float(loss * n), float(n)])
                step_losses.append((r, c, j, float(loss)))
                if r == 0 and j == 0:
                    gnorm0 = gn if gnorm0 is None else jax.tree.map(jnp.maximum, gnorm0, gn)
            new.append(p)
        losses.append(tot[0] / max(tot[1], 1.0))
        w = np.asarray(mask, np.float64) * np.asarray(n_ex, np.float64)
        if drop_client is not None:
            w[drop_client] = 0.0
        if w.sum() > 0:
            g = jax.tree.map(lambda *xs: sum(jnp.float32(wc / w.sum()) * x for wc, x in zip(w, xs)),
                             *new)
    host = lambda tree: {k: np.asarray(v, np.float32) for k, v in jax.device_get(tree).items()}  # noqa: E731
    return {"losses": [float(x) for x in losses], "trained": host(g), "start": host(start),
            "grad_norms": jax.device_get(gnorm0),
            # every local step's loss, (round, client, step, loss): for a look at the recipe
            "step_losses": step_losses}
