"""The plain reference of the latent-attention expert decoder under LoRA:
forward, loss, gradients, AdamW and the example-weighted mean, in
``jax.numpy``, float32 at ``highest`` (or a named precision), importing
nothing of the program.

The model never stands whole on the device: a client's step runs forward
through the layers keeping each layer's input, and backward a layer at a time
by ``jax.vjp`` with that layer's weights drawn again from the seed
(``weights.layer``), so one layer's float32 weights and one client's
activations are there at once. The held experts are a plain loop (a scan
over the held stack): expert e takes the ``cap`` rows with the largest
combine weight for e (the others of those rows weigh zero), and again, as
often as it still has rows to do (on the chip one expert drew 537 of a step's
4096 rows, five times the mean); ``cap`` is four times an expert's mean
share, or every row, where that is fewer. No row is dropped. What the absent experts would add is left out, as in the
program, and a padded position is routed to no expert.
"""

from __future__ import annotations

import functools
import math

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


def yarn_inv_freq(dim, theta, factor, original_max, beta_fast, beta_slow):
    """YaRN: the plain frequencies where a dimension turns more than
    ``beta_fast`` times over the original context, those over ``factor``
    where it turns fewer than ``beta_slow`` times, a linear ramp between."""
    pos = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction_dim(rotations):
        return dim * math.log(original_max / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return ((1.0 / (factor * pos)) * ramp + (1.0 / pos) * (1.0 - ramp)).astype(np.float32)


def softmax_scale(sizes):
    rs = sizes["rope_parameters"]
    m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
    return (sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]) ** -0.5 * m * m


def _rope(x, inv_freq):
    """Rotary positions over [B, S, heads, d], pairs (2j, 2j + 1)."""
    S = x.shape[1]
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq)[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).reshape(x.shape)


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _f32(x):
    return x.astype(jnp.float32)


def _static(sizes, precision, drop_expert=None):
    """What a jitted piece needs of the configuration, hashable."""
    d = weights.dims(sizes)
    rs = sizes["rope_parameters"]
    return (tuple(sorted((k, v) for k, v in d.items() if k != "held")), d["held"],
            sizes["rms_norm_eps"], softmax_scale(sizes), rs["llama_4_scaling_beta"],
            (rs["rope_theta"], rs["factor"], rs["original_max_position_embeddings"],
             rs["beta_fast"], rs["beta_slow"]), precision, drop_expert)


def rows_cap(T, k, E):
    return min(T, max(64, 4 * math.ceil(T * k / E)))


def layer_fwd(x, w, ad, mask, static):
    """One layer: ``x`` [B, S, H] -> ``y``; ``ad`` holds the layer's adapters
    under their short names (``dq.a`` ...)."""
    dd, held, eps, scale, beta, (theta, factor, orig, bf, bs), precision, drop = static
    d = dict(dd)
    op, act = ops(precision)
    B, S, H = x.shape
    heads, dn, dr, dv, kvr = d["heads"], d["dn"], d["dr"], d["dv"], d["kvr"]

    def mm(a, b):
        return jnp.matmul(op(a), op(b), precision=HI)

    def lin(h, name):
        xa = act(mm(h, ad[name + ".a"]))
        return act(act(mm(h, _f32(w[name]))) + act(mm(xa, ad[name + ".b"])))

    # ---- latent attention
    h = act(_rms(x, _f32(w["n1"]), eps))
    q = lin(act(_rms(lin(h, "dq"), _f32(w["qn"]), eps)), "uq").reshape(B, S, heads, dn + dr)
    ckv = lin(h, "dkv")
    kv = lin(act(_rms(ckv[..., :kvr], _f32(w["kvn"]), eps)), "ukv").reshape(B, S, heads, dn + dv)
    inv = yarn_inv_freq(dr, theta, factor, orig, bf, bs)
    pos = jnp.arange(S, dtype=jnp.float32)
    by_pos = 1.0 + beta * jnp.log1p(jnp.floor(pos / orig))
    extra = (scale * math.sqrt(dn + dr)) * by_pos[None, :, None, None]
    q = act(jnp.concatenate([q[..., :dn], act(_rope(q[..., dn:], inv))], -1) * extra)
    k_rope = act(_rope(ckv[..., kvr:][:, :, None, :], inv))
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_rope, (B, S, heads, dr))], -1)
    v = kv[..., dn:]
    ok = jnp.tril(jnp.ones((S, S), bool))[None] & (mask[:, None, :] > 0)
    bias = jnp.where(ok, 0.0, -1e30)[:, None]
    s = jnp.einsum("bqhd,bkhd->bhqk", op(q), op(k), precision=HI) / math.sqrt(dn + dr) + bias
    s = s - s.max(-1, keepdims=True)
    e = jnp.exp(s)
    p = e / (e.sum(-1, keepdims=True) + 1e-9)
    a = jnp.einsum("bhqk,bkhd->bqhd", op(act(p)), op(v), precision=HI)
    x = act(x + lin(act(a).reshape(B, S, heads * dv), "o"))

    # ---- expert layer
    h = act(_rms(x, _f32(w["n2"]), eps))
    shared = lin(act(jax.nn.silu(lin(h, "sg")) * lin(h, "su")), "sd")
    rows = h.reshape(B * S, H)
    T, k, E = B * S, d["k"], d["E"]
    probs = jax.nn.softmax(jnp.matmul(rows, _f32(w["r"]), precision=HI), axis=-1)
    top_p, top_i = lax.top_k(probs, k)
    cw = top_p / top_p.sum(-1, keepdims=True)
    cap = rows_cap(T, k, E)
    real = mask.reshape(T, 1) > 0

    @jax.checkpoint  # the backward pass keeps an expert's inputs and computes it again
    def expert(out, xs):
        e_id, eg, eu, ed = xs
        # [T]: zero where not assigned, and at a padded position (routed to
        # no expert)
        w_e = jnp.where((top_i == e_id) & real, cw, 0.0).sum(-1)
        if drop is not None:
            w_e = jnp.where(e_id == drop, 0.0, w_e)

        def some_rows(state):
            """The ``cap`` rows that weigh most among those still to do."""
            left, out = state
            vals, at = lax.top_k(left, cap)
            xr = rows[at]
            a_ = act(jax.nn.silu(act(mm(xr, _f32(eg)))) * act(mm(xr, _f32(eu))))
            return left.at[at].set(0.0), out.at[at].add(vals[:, None] * act(mm(a_, _f32(ed))))

        state = (w_e, out)
        for _ in range(-(-T // cap)):  # as many passes as the fullest expert needs
            state = lax.cond((lax.stop_gradient(state[0]) > 0).any(), some_rows,
                             lambda st: st, state)
        return state[1], None

    routed, _ = lax.scan(expert, jnp.zeros((T, H), jnp.float32),
                         (jnp.asarray(held, jnp.int32), w["eg"], w["eu"], w["ed"]))
    return act(x + act(shared + act(routed).reshape(B, S, H)))


def head_logits(x, we, ad, static):
    """Final norm and head: float32 logits [B, S, V]."""
    _, _, eps, *_, precision, _ = static
    op, act = ops(precision)
    x = act(_rms(x, _f32(we["norm"]), eps))
    xa = act(jnp.matmul(op(x), op(ad["lm.a"]), precision=HI))
    return (jnp.matmul(op(x), op(_f32(we["lm"])), precision=HI)
            + jnp.matmul(op(xa), op(ad["lm.b"]), precision=HI))


def head_loss(x, we, ad, batch, static):
    """Next-token cross-entropy over the head's logits: ``(loss, (correct,
    tokens))``, a target counted where it is a real token of a real
    example."""
    logits = head_logits(x, we, ad, static)[:, :-1]
    targets = batch["ids"][:, 1:]
    wt = _f32(batch["mask"][:, 1:]) * _f32(batch["example_mask"])[:, None]
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


def logits(trained, sizes, seed, batch, precision="f32"):
    """The forward pass alone: float32 logits [B, S, V]."""
    static = _static(sizes, precision)
    we = weights.ends(sizes, seed)
    x = _embed(we["emb"], batch["ids"], "f32" if precision == "f32" else "bf16")
    for i in range(weights.dims(sizes)["L"]):
        n = f"L{i}."
        ad = {k[len(n):]: v for k, v in trained.items() if k.startswith(n)}
        x = _fwd(x, weights.layer(sizes, seed, i), ad, batch["mask"], static)
    return head_logits(x, we, {k: trained[k] for k in ("lm.a", "lm.b")}, static)


def loss_and_grad(trained, sizes, seed, batch, precision="f32", drop_expert=None):
    """``(loss, tokens, grads)`` of one client's batch: ``grads`` in the
    flat naming of ``trained``."""
    static = _static(sizes, precision, drop_expert)
    L = weights.dims(sizes)["L"]
    we = weights.ends(sizes, seed)

    def of_layer(i):
        n = f"L{i}."
        return {k[len(n):]: v for k, v in trained.items() if k.startswith(n)}

    xs = [_embed(we["emb"], batch["ids"], "f32" if precision == "f32" else "bf16")]
    for i in range(L):
        xs.append(_fwd(xs[-1], weights.layer(sizes, seed, i), of_layer(i), batch["mask"], static))
    head_ad = {k: trained[k] for k in ("lm.a", "lm.b")}
    loss, (_, n), dx, grads = _head(xs[-1], we, head_ad, batch, static)
    grads = dict(grads)
    for i in reversed(range(L)):
        dx, dad = _bwd(xs[i], weights.layer(sizes, seed, i), of_layer(i), batch["mask"], dx, static)
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
               drop_client=None, drop_expert=None):
    """The first ``len(masks)`` rounds: every client's local AdamW steps over
    the adapters from the round's global ones with a fresh state, then the
    example-weighted mean under the round's mask; one client and one step at
    a time. The adapters stay float32 throughout."""
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
                    B = b["ids"].shape[0]
                    b = dict(b, example_mask=b["example_mask"] * (jnp.arange(B) < max(B // 2, 1)))
                loss, n, grads = loss_and_grad(p, sizes, seed, b, precision, drop_expert)
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
