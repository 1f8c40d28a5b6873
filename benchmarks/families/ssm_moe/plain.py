"""The plain reference of the state-space and attention hybrid decoder under
LoRA (``granitemoehybrid``): forward, loss, gradients, AdamW and the
example-weighted mean, in ``jax.numpy``, float32 at ``highest`` (or a named
precision), importing nothing of the program and nothing of another family.

``x0 = embedding_multiplier * emb[ids]``; a layer is ``h = x + r Mix(rms(x))``,
``y = h + r (MoE(n) + Shared(n))``, ``n = rms(h)``; ``logits = (rms(x_L) emb^T
+ adapter) / logits_scaling``. ``Mix`` is the Mamba-2 mixer or attention
without positions, by the configuration's ``layer_types``.

- The recurrence runs A POSITION AT A TIME, the definition: ``S_t = exp(dt_t
  A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``, a ``lax.scan`` over
  the positions of a row, in blocks of ``mamba_chunk_size`` positions under
  ``jax.checkpoint`` so that the backward pass holds one block's states. No
  chunked form, no cumulative sums.
- Attention: a masked softmax a head (a ``lax.map`` over the query heads, so
  one head's [S, S] scores stand at once), key-value head ``h // (heads /
  kv_heads)``, scale ``attention_multiplier``, no position term.
- The router in the published order: the top k of the logits, then their
  softmax (the program: softmax over all, top k, renormalised; equal up to
  rounding). The held experts are a plain loop with a mask (a scan over the
  held stack, every row through every held expert, weighed by its combine
  weight or zero). What the absent experts would add is left out, as in the
  program, and a padded position is routed to no expert.
- The model never stands whole on the device: a client's step runs forward
  through the layers keeping each layer's input, and backward a layer at a
  time by ``jax.vjp`` with that layer's weights drawn again from the seed.

Departures from the published model: none in the equations. Assumed, as the
configuration's file says: no clamp on ``dt``; weights from the seed
(``weights.py``). In a precision below float32 (``ops``) the matrix products
round their operands and the pipeline its activations; inside the recurrence
the state stays float32 and ``dt x`` and the state as ``C``'s operand are
rounded as activations.
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


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _f32(x):
    return x.astype(jnp.float32)


Static = collections.namedtuple("Static", (
    "dims", "held", "kind", "eps", "attn_scale", "residual", "logits_divisor", "precision",
    "drop_expert", "no_carry"))


def _static(sizes, kind, precision, drop_expert=None, no_carry=False):
    """What a jitted piece needs of the configuration, hashable."""
    d = weights.dims(sizes)
    return Static(tuple(sorted((k, v) for k, v in d.items() if k not in ("held", "kinds"))),
                  d["held"], kind, sizes["rms_norm_eps"], sizes["attention_multiplier"],
                  sizes["residual_multiplier"], sizes["logits_scaling"], precision, drop_expert,
                  no_carry)


# ------------------------------------------------------------- the mixers

def recurrence(x, dt, A, Bm, Cm, D, block, act, no_carry=False):
    """The selective state-space recurrence, a position at a time: ``x``
    [B, S, heads, P], ``dt`` [B, S, heads], ``A``/``D`` [heads], ``Bm``/``Cm``
    [B, S, N] -> ``y`` as ``x``. ``no_carry`` plants a fault: the state is
    NOT carried from one block of ``block`` positions to the next (every
    block starts from zero)."""
    S = x.shape[1]
    pad = -S % block

    def row(x, dt, Bm, Cm):
        if pad:  # dt 0: nothing decays and nothing is added
            x, dt, Bm, Cm = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                             for a in (x, dt, Bm, Cm))
        cut = lambda a: a.reshape((-1, block) + a.shape[1:])  # noqa: E731

        def step(state, t):
            xt, dtt, bt, ct = t
            state = (jnp.exp(dtt * A)[:, None, None] * state
                     + act(dtt[:, None] * xt)[:, :, None] * bt[None, None, :])
            return state, (act(state) * ct[None, None, :]).sum(-1) + D[:, None] * xt

        @jax.checkpoint  # the backward pass holds one block's states
        def a_block(state, ts):
            if no_carry:
                state = jnp.zeros_like(state)
            # unrolled eight positions a loop iteration: the same sequential
            # updates, fewer trips through the device's loop machinery
            return lax.scan(step, state, ts, unroll=8)

        state0 = jnp.zeros(x.shape[1:] + (Bm.shape[-1],), jnp.float32)
        _, y = lax.scan(a_block, state0, (cut(x), cut(dt), cut(Bm), cut(Cm)))
        return y.reshape((-1,) + y.shape[2:])[:S]

    return jax.vmap(row)(x, dt, Bm, Cm)


def mamba_mix(h, w, lin, d, eps, act, no_carry):
    B, S, _ = h.shape
    di, N, K, Hm, P = d["di"], d["N"], d["K"], d["Hm"], d["P"]
    zxbcdt = lin(h, "in")
    z, xbc, dt = zxbcdt[..., :di], zxbcdt[..., di:2 * di + 2 * N], zxbcdt[..., 2 * di + 2 * N:]
    # depthwise, causal: conv[k] weighs the position K - 1 - k back
    padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = _f32(w["conv"])
    xbc = act(jax.nn.silu(_f32(w["convb"]) + sum(conv[k] * padded[:, k:k + S] for k in range(K))))
    dt = jax.nn.softplus(dt + _f32(w["dtb"]))
    y = recurrence(xbc[..., :di].reshape(B, S, Hm, P), dt, -jnp.exp(_f32(w["alog"])),
                   xbc[..., di:di + N], xbc[..., di + N:], _f32(w["D"]), d["chunk"], act,
                   no_carry)
    gated = act(_rms(act(y).reshape(B, S, di) * jax.nn.silu(z), _f32(w["gn"]), eps))
    return lin(gated, "out")


def attention_mix(h, mask, lin, d, scale, op, act):
    B, S, _ = h.shape
    heads, kv, hd = d["heads"], d["kv"], d["hd"]
    q = lin(h, "q").reshape(B, S, heads, hd)
    k = lin(h, "k").reshape(B, S, kv, hd)
    v = lin(h, "v").reshape(B, S, kv, hd)
    ok = jnp.tril(jnp.ones((S, S), bool))[None] & (mask[:, None, :] > 0)
    bias = jnp.where(ok, 0.0, -1e30)  # [B, S, S]

    @jax.checkpoint
    def a_head(i):
        qi = q[:, :, i]
        ki, vi = (lax.dynamic_index_in_dim(a, i // (heads // kv), 2, keepdims=False)
                  for a in (k, v))
        s = jnp.einsum("bqd,bkd->bqk", op(qi), op(ki), precision=HI) * scale + bias
        s = s - s.max(-1, keepdims=True)
        e = jnp.exp(s)
        p = e / (e.sum(-1, keepdims=True) + 1e-9)
        return jnp.einsum("bqk,bkd->bqd", op(act(p)), op(vi), precision=HI)

    a = lax.map(a_head, jnp.arange(heads))  # [heads, B, S, hd]
    return lin(act(a).transpose(1, 2, 0, 3).reshape(B, S, heads * hd), "o")


# --------------------------------------------------------------- a layer

def layer_fwd(x, w, ad, mask, static):
    """One layer: ``x`` [B, S, H] -> ``y``; ``ad`` holds the layer's adapters
    under their short names (``in.a`` ...)."""
    held, kind, eps, rm, drop = static.held, static.kind, static.eps, static.residual, static.drop_expert
    d = dict(static.dims)
    op, act = ops(static.precision)
    B, S, H = x.shape

    def mm(a, b):
        return jnp.matmul(op(a), op(b), precision=HI)

    def lin(h, name):
        xa = act(mm(h, ad[name + ".a"]))
        return act(act(mm(h, _f32(w[name]))) + act(mm(xa, ad[name + ".b"])))

    h = act(_rms(x, _f32(w["n1"]), eps))
    if kind == "mamba":
        mixed = mamba_mix(h, w, lin, d, eps, act, static.no_carry)
    else:
        mixed = attention_mix(h, mask, lin, d, static.attn_scale, op, act)
    x = act(x + act(rm * mixed))

    # ---- expert layer
    h = act(_rms(x, _f32(w["n2"]), eps))
    Fs, F = d["Fs"], d["F"]
    gu = lin(h, "si")
    shared = lin(act(jax.nn.silu(gu[..., :Fs]) * gu[..., Fs:]), "so")
    rows = h.reshape(B * S, H)
    top_l, top_i = lax.top_k(jnp.matmul(rows, _f32(w["r"]), precision=HI), d["k"])
    cw = jax.nn.softmax(top_l, axis=-1)
    real = mask.reshape(B * S, 1) > 0

    @jax.checkpoint  # the backward pass keeps an expert's inputs and computes it again
    def expert(out, xs):
        e_id, ei, eo = xs
        # [T]: zero where not assigned, and at a padded position
        w_e = jnp.where((top_i == e_id) & real, cw, 0.0).sum(-1)
        if drop is not None:
            w_e = jnp.where(e_id == drop, 0.0, w_e)
        g_u = act(mm(rows, _f32(ei)))
        a_ = act(jax.nn.silu(g_u[:, :F]) * g_u[:, F:])
        return out + w_e[:, None] * act(mm(a_, _f32(eo))), None

    routed, _ = lax.scan(expert, jnp.zeros((B * S, H), jnp.float32),
                         (jnp.asarray(held, jnp.int32), w["ei"], w["eo"]))
    return act(x + act(rm * act(shared + act(routed).reshape(B, S, H))))


def head_logits(x, we, ad, static):
    """Final norm and the tied head: float32 logits [B, S, V]."""
    op, act = ops(static.precision)
    x = act(_rms(x, _f32(we["norm"]), static.eps))
    xa = act(jnp.matmul(op(x), op(ad["lm.a"]), precision=HI))
    return (jnp.matmul(op(x), op(_f32(we["emb"])).T, precision=HI)
            + jnp.matmul(op(xa), op(ad["lm.b"]), precision=HI)) / static.logits_divisor


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


@functools.partial(jax.jit, static_argnames=("multiplier", "act"))
def _embed(emb, ids, multiplier, act):
    return ops(act)[1](multiplier * _f32(emb)[ids])


def _of_layer(trained, i):
    n = f"L{i}."
    return {k[len(n):]: v for k, v in trained.items() if k.startswith(n)}


def _forward(trained, sizes, seed, batch, precision, keep, **fault):
    """Through the layers; ``keep``: every layer's input, for the backward
    pass. Returns ``(xs, statics, ends)``."""
    kinds = weights.dims(sizes)["kinds"]
    statics = [_static(sizes, kind, precision, **fault) for kind in kinds]
    we = weights.ends(sizes, seed)
    xs = [_embed(we["emb"], batch["ids"], float(sizes["embedding_multiplier"]),
                 "f32" if precision == "f32" else "bf16")]
    for i, static in enumerate(statics):
        y = _fwd(xs[-1], weights.layer(sizes, seed, i), _of_layer(trained, i), batch["mask"], static)
        xs = xs + [y] if keep else [y]
    return xs, statics, we


def logits(trained, sizes, seed, batch, precision="f32"):
    """The forward pass alone: float32 logits [B, S, V]."""
    xs, statics, we = _forward(trained, sizes, seed, batch, precision, keep=False)
    return head_logits(xs[-1], we, {k: trained[k] for k in ("lm.a", "lm.b")}, statics[-1])


def loss_and_grad(trained, sizes, seed, batch, precision="f32", drop_expert=None, no_carry=False):
    """``(loss, tokens, grads)`` of one client's batch: ``grads`` in the
    flat naming of ``trained``."""
    xs, statics, we = _forward(trained, sizes, seed, batch, precision, keep=True,
                               drop_expert=drop_expert, no_carry=no_carry)
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
               drop_client=None, drop_expert=None, no_carry=False):
    """The first ``len(masks)`` rounds: every client's local AdamW steps over
    the adapters from the round's global ones with a fresh state, then the
    example-weighted mean under the round's mask; one client and one step at
    a time. The adapters stay float32 throughout. The planted faults:
    ``half_batch`` (half of a batch's rows feed the loss; of a batch of one
    row, the first half of its targets), ``drop_client``, ``drop_expert``
    (held expert e's part left out) and ``no_carry`` (``recurrence``)."""
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
                loss, n, grads = loss_and_grad(p, sizes, seed, b, precision, drop_expert, no_carry)
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
