"""A whole model family added as new files (benchmarks/tests/test_extend.py
copies this package to ``benchmarks/families/decoder_lora`` of a temporary
copy): a Llama-style decoder (RMSNorm, rotary positions, grouped-query
attention, SwiGLU, an LM head) trained as a causal-LM job under LoRA over a
frozen base held in bfloat16. The interface is
``benchmarks/families/__init__.py``'s. It imports nothing of the program.

Flat naming. The base (frozen, in ``training.param_dtype``):
  emb [V, H]   L<i>.n1 / L<i>.n2 / norm [H]
  L<i>.q [H, nh*hd]  L<i>.k / L<i>.v [H, kvh*hd]  L<i>.o [nh*hd, H]
  L<i>.gate / L<i>.up [H, F]  L<i>.down [F, H]   lm [H, V]
What is trained, held in the type the program draws it in, the base's
(``bcfl_tpu/models/lora.py``): for each matrix ``m`` above but ``emb`` the
adapter ``m.a`` [fan_in, r] and ``m.b`` [r, fan_out]; the matrix the model
applies is ``m + m.a @ m.b``. ``b`` starts from the seed and not from zeros,
so both factors have a gradient in the first step; both start at the
base's own scale (``initializer_range``), where a step of the
configuration's learning rate is several of the stored type's roundings.

The configuration names its cuts in keys of its own (``layers``,
``vocab_rows``) and this file maps them to the program's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks import yardstick

WEIGHT_LANE = 0x10AA
TARGETS = ("q", "k", "v", "o", "gate", "up", "down")
PROGRAM_NAMES = {"q": ("attention", "q_proj"), "k": ("attention", "k_proj"),
                 "v": ("attention", "v_proj"), "o": ("attention", "o_proj"),
                 "gate": ("mlp", "gate_proj"), "up": ("mlp", "up_proj"),
                 "down": ("mlp", "down_proj")}


def program(sizes):
    return {"model": sizes["program_model"], "vocab_size": sizes["vocab_rows"],
            "num_labels": 2, "task": "causal_lm", "lora_rank": sizes["lora"]["r"]}


def precisions(sizes):
    p = sizes["training"]["reference_precisions"]
    return p["stated"], p["control"]


def _dims(sizes):
    H, nh, kvh = sizes["hidden_size"], sizes["num_attention_heads"], sizes["num_key_value_heads"]
    hd = H // nh
    return H, nh, kvh, hd, sizes["intermediate_size"], sizes["vocab_rows"], sizes["layers"]


def _matrices(sizes):
    """``{name: (fan_in, fan_out)}`` of every matrix that carries an adapter."""
    H, nh, kvh, hd, F, V, L = _dims(sizes)
    out = {}
    for i in range(L):
        out.update({f"L{i}.q": (H, nh * hd), f"L{i}.k": (H, kvh * hd), f"L{i}.v": (H, kvh * hd),
                    f"L{i}.o": (nh * hd, H), f"L{i}.gate": (H, F), f"L{i}.up": (H, F),
                    f"L{i}.down": (F, H)})
    out["lm"] = (H, V)
    return out


def _spec(sizes):
    """``((name, shape, kind, dtype), ...)`` of every array, base then adapters."""
    H, _, _, _, _, V, L = _dims(sizes)
    base = sizes["training"]["param_dtype"]
    r = sizes["lora"]["r"]
    spec = [("emb", (V, H), "normal", base), ("norm", (H,), "ones", base)]
    for i in range(L):
        spec += [(f"L{i}.n1", (H,), "ones", base), (f"L{i}.n2", (H,), "ones", base)]
    for name, (fi, fo) in _matrices(sizes).items():
        spec += [(name, (fi, fo), "normal", base),
                 (name + ".a", (fi, r), "normal", base), (name + ".b", (r, fo), "normal", base)]
    return tuple(spec)


@functools.partial(jax.jit, static_argnames=("spec", "std"))
def _make(seed, spec, std):
    key = jax.random.fold_in(jax.random.key(seed), WEIGHT_LANE)
    out = {}
    for i, (name, shape, kind, dtype) in enumerate(spec):
        if kind == "ones":
            x = jnp.ones(shape, jnp.float32)
        else:
            x = std * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        out[name] = x.astype(dtype)
    return out


def make_weights(sizes, seed):
    """Every array from the seed in one jitted call, in the type the
    configuration states for the program's parameters."""
    return _make(jnp.uint32(int(seed) % (2 ** 32)), _spec(sizes),
                 float(sizes["initializer_range"]))


def _trained(flat):
    return {k: v for k, v in flat.items() if k.endswith((".a", ".b"))}


def to_program(flat, sizes):
    """The program's trees (``bcfl_tpu/models/llama.py``'s flax names and
    ``models/lora.py``'s adapter keys); DenseGeneral keeps heads apart."""
    H, nh, kvh, hd, _, _, L = _dims(sizes)
    heads = {"q": nh, "k": kvh, "v": kvh}
    model = {"embed": {"embedding": flat["emb"]}, "final_norm": {"scale": flat["norm"]}}
    adapters = {"lm_head": {"a": flat["lm.a"], "b": flat["lm.b"]}}
    for i in range(L):
        layer = {"attention": {}, "mlp": {}, "input_norm": {"scale": flat[f"L{i}.n1"]},
                 "post_attention_norm": {"scale": flat[f"L{i}.n2"]}}
        for m in TARGETS:
            w = flat[f"L{i}.{m}"]
            if m in heads:
                w = w.reshape(H, heads[m], hd)
            elif m == "o":
                w = w.reshape(nh, hd, H)
            group, theirs = PROGRAM_NAMES[m]
            layer[group][theirs] = {"kernel": w}
            adapters[f"model/layer_{i}/{group}/{theirs}"] = {
                "a": flat[f"L{i}.{m}.a"], "b": flat[f"L{i}.{m}.b"]}
        model[f"layer_{i}"] = layer
    return adapters, {"model": model, "lm_head": {"kernel": flat["lm"]}}


def from_program(trainable, sizes):
    out = {"lm.a": trainable["lm_head"]["a"], "lm.b": trainable["lm_head"]["b"]}
    for i in range(sizes["layers"]):
        for m in TARGETS:
            group, theirs = PROGRAM_NAMES[m]
            ab = trainable[f"model/layer_{i}/{group}/{theirs}"]
            out[f"L{i}.{m}.a"], out[f"L{i}.{m}.b"] = ab["a"], ab["b"]
    return out


# ---------------------------------------------------------------- the model

def _bf16(x):
    return x + lax.stop_gradient(lax.reduce_precision(x, 8, 7) - x)


def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 240.0
    return x + lax.stop_gradient(lax.reduce_precision(x / s, 4, 3) * s - x)


def _ops(precision):
    """``(operand, act)``: how a matrix unit sees an operand and how the
    pipeline holds an activation. "f32": as they are, at ``highest``;
    "bf16": both rounded to bfloat16 (what the configuration states);
    "fp8": operands to an 8-bit float inside the bfloat16 pipeline."""
    ident = lambda x: x  # noqa: E731
    return {"f32": (ident, ident), "bf16": (_bf16, _bf16), "fp8": (_fp8, _bf16)}[precision]


def _rope(x, theta):
    """Rotary positions over [B, S, heads, hd], pairs (2j, 2j + 1)."""
    S, D = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).reshape(x.shape)


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def loss_fn(trained, base, sizes, batch, precision="f32", skip=None):
    """Next-token cross-entropy of the decoder with every adapter merged
    (``skip`` names one matrix whose adapter is left out: a fault).
    ``(loss, (correct, tokens))``, weighted as the job states: a target
    counts where it is a real token of a real example."""
    H, nh, kvh, hd, _, _, L = _dims(sizes)
    op, act = _ops(precision)
    eps, theta = sizes["rms_norm_eps"], sizes["rope_theta"]
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731

    def w(name):
        m = f32(base[name])
        if name != skip:
            # the merged matrix is held in the base's type
            m = act(m + act(jnp.matmul(f32(trained[name + ".a"]), f32(trained[name + ".b"]),
                                       precision=lax.Precision.HIGHEST)))
        return m

    def mm(x, name):
        return jnp.matmul(op(x), op(w(name)), precision=lax.Precision.HIGHEST)

    ids, mask = batch["ids"], batch["mask"]
    B, S = ids.shape
    ok = jnp.tril(jnp.ones((S, S), bool))[None] & (mask[:, None, :] > 0)
    bias = jnp.where(ok, 0.0, -1e30)[:, None]
    x = act(f32(base["emb"])[ids])
    for i in range(L):
        n = f"L{i}."
        h = act(_rms(x, f32(base[n + "n1"]), eps))
        q = _rope(act(mm(h, n + "q")).reshape(B, S, nh, hd), theta)
        k = _rope(act(mm(h, n + "k")).reshape(B, S, kvh, hd), theta)
        v = act(mm(h, n + "v")).reshape(B, S, kvh, hd)
        k, v = (jnp.repeat(t, nh // kvh, axis=2) for t in (k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", op(act(q)), op(act(k)),
                       precision=lax.Precision.HIGHEST) / jnp.sqrt(jnp.float32(hd)) + bias
        s = s - s.max(-1, keepdims=True)
        e = jnp.exp(s)
        p = e / (e.sum(-1, keepdims=True) + 1e-9)
        a = jnp.einsum("bhqk,bkhd->bqhd", op(act(p)), op(v), precision=lax.Precision.HIGHEST)
        x = act(x + act(mm(act(a).reshape(B, S, nh * hd), n + "o")))
        h = act(_rms(x, f32(base[n + "n2"]), eps))
        g = act(jax.nn.silu(act(mm(h, n + "gate"))) * act(mm(h, n + "up")))
        x = act(x + act(mm(g, n + "down")))
    x = act(_rms(x, f32(base["norm"]), eps))
    # the head computes in float32 in every precision the configuration states
    logits = jnp.matmul(x, w("lm"), precision=lax.Precision.HIGHEST)[:, :-1]
    targets = ids[:, 1:]
    wt = f32(mask[:, 1:]) * f32(batch["example_mask"])[:, None]
    logp = jax.nn.log_softmax(logits, axis=-1)
    per_tok = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    n = jnp.maximum(wt.sum(), 1.0)
    correct = ((jnp.argmax(logits, -1) == targets) * wt).sum()
    return (per_tok * wt).sum() / n, (correct, wt.sum())


# --------------------------------------------------------------- the rounds

@functools.partial(jax.jit, static_argnames=("sizes_key", "precision", "half_batch", "skip"))
def _local_step(trained, mu, nu, t, base, batch, hp, sizes_key, precision, half_batch, skip):
    sizes = dict(sizes_key)
    if half_batch:
        B = batch["ids"].shape[0]
        batch = dict(batch, example_mask=batch["example_mask"] * (jnp.arange(B) < B // 2))
    # the arithmetic in float32 whatever type the adapters are stored in
    stored = jax.tree.map(lambda x: x.dtype, trained)
    trained = jax.tree.map(lambda x: x.astype(jnp.float32), trained)
    (loss, (_, n)), g = jax.value_and_grad(loss_fn, has_aux=True)(
        trained, base, sizes, batch, precision, skip)
    t = t + 1
    mu = jax.tree.map(lambda m, x: hp["b1"] * m + (1 - hp["b1"]) * x, mu, g)
    nu = jax.tree.map(lambda v, x: hp["b2"] * v + (1 - hp["b2"]) * x * x, nu, g)
    c1, c2 = 1 - hp["b1"] ** t, 1 - hp["b2"] ** t
    trained = jax.tree.map(
        lambda p, m, v: p - hp["lr"] * ((m / c1) / (jnp.sqrt(v / c2) + hp["eps"]) + hp["wd"] * p),
        trained, mu, nu)
    trained = jax.tree.map(lambda x, d: x.astype(d), trained, stored)
    return trained, mu, nu, t, loss * n, n, jax.tree.map(lambda x: jnp.sqrt(jnp.sum(x * x)), g)


def reference(sizes, seed, batches, masks, n_ex, precision=None, fault=None):
    """The first rounds: every client's local AdamW steps over the adapters
    from the round's global ones with a fresh state, then the example-weighted
    mean under the round's mask. The frozen base is made from the seed here
    and held once, in the type the configuration states; so are the adapters
    between steps and after the mean (the arithmetic and AdamW's moments are
    float32); one client and one step at a time."""
    fault = fault or {}
    flat = make_weights(sizes, seed)
    base = {k: v for k, v in flat.items() if not k.endswith((".a", ".b"))}
    start = _trained(flat)
    tr = sizes["training"]
    hp = {k: jnp.float32(tr[v]) for k, v in (("lr", "learning_rate"), ("b1", "b1"), ("b2", "b2"),
                                             ("eps", "eps"), ("wd", "weight_decay"))}
    key = tuple((k, v) for k, v in sizes.items() if isinstance(v, (int, float)))
    batches = jax.tree.map(jnp.asarray, batches)
    C, steps = batches["ids"].shape[:2]
    g, losses, gnorm0 = start, [], None
    for r, mask in enumerate(masks):
        new, tot = [], np.zeros(2)
        for c in range(C):
            p = g
            mu = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), p)
            nu, t = mu, jnp.float32(0)
            for j in range(steps):
                b = {k: v[c, j] for k, v in batches.items()}
                p, mu, nu, t, ln, n, gn = _local_step(
                    p, mu, nu, t, base, b, hp, key, precision or "f32",
                    bool(fault.get("half_batch")), fault.get("adapter_not_applied"))
                tot += np.array([float(ln), float(n)])
                if r == 0 and j == 0:
                    gnorm0 = gn if gnorm0 is None else jax.tree.map(jnp.maximum, gnorm0, gn)
            new.append(p)
        losses.append(tot[0] / max(tot[1], 1.0))
        w = np.asarray(mask, np.float64) * np.asarray(n_ex, np.float64)
        if fault.get("drop_client") is not None:
            w[fault["drop_client"]] = 0.0
        if w.sum() > 0:
            g = jax.tree.map(
                lambda *xs: sum(jnp.float32(wc / w.sum()) * x.astype(jnp.float32)
                                for wc, x in zip(w, xs)).astype(xs[0].dtype), *new)
    host = lambda tree: {k: np.asarray(v, np.float32) for k, v in jax.device_get(tree).items()}  # noqa: E731
    return {"losses": [float(x) for x in losses], "trained": host(g), "start": host(start),
            "grad_norms": jax.device_get(gnorm0)}


# ------------------------------------------------------ required operations

def forward_flops_per_token(sizes, seq):
    """Every product of one forward pass, the adapters' among them."""
    return sum(f for f, _ in _products(sizes, seq))


def _products(sizes, seq):
    """``(forward FLOP a token, trained?)`` of every product: a frozen
    matrix, its two adapter factors, and attention's two products of
    activations (which have a gradient for each operand, as a trained one)."""
    H, nh, _, hd, _, _, L = _dims(sizes)
    r = sizes["lora"]["r"]
    out = []
    for fi, fo in _matrices(sizes).values():
        out += [(2 * fi * fo, False), (2 * fi * r + 2 * r * fo, True)]
    out += [(4 * seq * nh * hd, True)] * L
    return out


def train_flops_per_token(sizes, seq, cell=None):
    return sum(yardstick.train_flops(f, trained) for f, trained in _products(sizes, seq))
