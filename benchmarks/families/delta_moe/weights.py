"""Weights from the seed for the Gated DeltaNet and gated attention decoder
with experts, a layer at a time, and their layout as the program's trees.

Flat naming. The base (frozen, in ``training.param_dtype``):
  emb [V, H]   head [H, V] (untied)   norm [H]
  L<i>.n1 / L<i>.n2 [H]
  a Gated DeltaNet layer (``linear_attention``):
    L<i>.qkvz [H, Hk (2 Dk + 2 (Hv / Hk) Dv)]   (a key head at a time: q, k, v, z; as published)
    L<i>.ba [H, 2 Hv]   (a key head at a time: b, a)
    L<i>.conv [d_conv, 2 Hk Dk + Hv Dv]   (channels [q | k | v]; no bias)
    L<i>.alog / L<i>.dtb [Hv]   L<i>.gn [Dv]   L<i>.out [Hv Dv, H]
  a full-attention layer:
    L<i>.q [H, heads 2 D] (a head at a time: q, gate)   L<i>.k / L<i>.v [H, kv_heads D]
    L<i>.o [heads D, H]   L<i>.qn / L<i>.kn [D]
  every layer:
    L<i>.sg / L<i>.su [H, Fs]   L<i>.sd [Fs, H]   (the shared expert)
    L<i>.sgate [H, 1]   (the shared expert's gate)   L<i>.r [H, E]   (router)
    L<i>.eg / L<i>.eu [G, H, F]   L<i>.ed [G, F, H]   (the G experts HELD, in
        ascending order of their index; expert e's draw depends on e and not
        on which others are held)
What is trained, in float32: for each adapted matrix (a Gated DeltaNet
layer's qkvz and out, an attention layer's q, k, v, o, every layer's sg, su
and sd, and the head, ``lm``) the adapter ``m.a`` [fan_in, r] and ``m.b``
[r, fan_out]; the model applies ``x m + (x m.a) m.b``. Both factors start
from the seed, nonzero, so both have a gradient in the first step.

Every matrix and the embedding normal(0, ``initializer_range``). The norms
at their starts: the zero-centred ones (n1, n2, norm, qn, kn: ``(1 + w)``) at
0, the gated norm's ``gn`` at 1. The rule's own parameters, as the
configuration's ``assumed`` says: ``A`` uniform in (0, 16) stored as ``log
A`` (the published draw); ``dt`` log-uniform in [0.001, 0.1] stored as the
value whose softplus it is (the GatedDeltaNet reference initialiser, not the
published placeholder 1); the convolution uniform in +-1/sqrt(d_conv). The
base never stands whole on the device here: ``make`` draws a layer on the
device and fetches it, and the reference draws a layer again when it
reaches it (``layer``).
"""

from __future__ import annotations

import functools
import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

WEIGHT_LANE = 0x474444  # the weights' lane of the seed
LINEAR, FULL = "linear_attention", "full_attention"
SHARED = ("sg", "su", "sd")
ADAPTED = {LINEAR: ("qkvz", "out") + SHARED, FULL: ("q", "k", "v", "o") + SHARED}
PROGRAM_NAMES = {"qkvz": ("linear_attn", "in_proj_qkvz"), "out": ("linear_attn", "out_proj"),
                 "q": ("self_attn", "q_proj"), "k": ("self_attn", "k_proj"),
                 "v": ("self_attn", "v_proj"), "o": ("self_attn", "o_proj"),
                 "sg": ("moe", "shared_experts", "gate_proj"),
                 "su": ("moe", "shared_experts", "up_proj"),
                 "sd": ("moe", "shared_experts", "down_proj")}


def kinds(sizes):
    """The published pattern's first ``layers``: full attention every
    ``full_attention_interval``-th layer."""
    n = sizes["full_attention_interval"]
    return tuple(FULL if (i + 1) % n == 0 else LINEAR for i in range(sizes["layers"]))


def dims(sizes):
    """The sizes by short names, the cut applied."""
    held = held_experts(sizes)
    hk, hv = sizes["linear_num_key_heads"], sizes["linear_num_value_heads"]
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    hd = sizes["head_dim"]
    return dict(
        H=sizes["hidden_size"], L=sizes["layers"], V=sizes["vocab_rows"], kinds=kinds(sizes),
        heads=sizes["num_attention_heads"], kv=sizes["num_key_value_heads"], hd=hd,
        rot=int(hd * sizes["partial_rotary_factor"]),
        Hk=hk, Hv=hv, Dk=dk, Dv=dv, K=sizes["linear_conv_kernel_dim"],
        conv=2 * hk * dk + hv * dv, chunk=sizes["gdn_chunk_size"],
        E=sizes["num_experts"], k=sizes["num_experts_per_tok"],
        F=sizes["moe_intermediate_size"], Fs=sizes["shared_expert_intermediate_size"],
        held=held, G=len(held), r=sizes["lora"]["r"])


def held_experts(sizes):
    """The held experts' indices: a count n means experts 0 .. n-1 (the
    first of E / n equal shares), a list names them."""
    h = sizes["experts_held"]
    return tuple(range(h)) if isinstance(h, int) else tuple(sorted(h))


def matrix_shapes(sizes, kind):
    """``{short name: (fan_in, fan_out)}`` of the 2-D matrices of a layer of
    ``kind``, all normal(0, initializer_range)."""
    d = dims(sizes)
    H = d["H"]
    if kind == LINEAR:
        mixer = {"qkvz": (H, 2 * d["Hk"] * d["Dk"] + 2 * d["Hv"] * d["Dv"]),
                 "ba": (H, 2 * d["Hv"]), "out": (d["Hv"] * d["Dv"], H)}
    else:
        mixer = {"q": (H, 2 * d["heads"] * d["hd"]), "k": (H, d["kv"] * d["hd"]),
                 "v": (H, d["kv"] * d["hd"]), "o": (d["heads"] * d["hd"], H)}
    return {**mixer, "sg": (H, d["Fs"]), "su": (H, d["Fs"]), "sd": (d["Fs"], H),
            "sgate": (H, 1), "r": (H, d["E"])}


def adapted_shapes(sizes):
    """``{flat name: (fan_in, fan_out)}`` of every matrix with an adapter."""
    d = dims(sizes)
    out = {}
    for i, kind in enumerate(d["kinds"]):
        m = matrix_shapes(sizes, kind)
        out.update({f"L{i}.{n}": m[n] for n in ADAPTED[kind]})
    out["lm"] = (d["H"], d["V"])
    return out


def _key(seed):
    return jax.random.fold_in(jax.random.key(seed), WEIGHT_LANE)


def _normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


@functools.partial(jax.jit, static_argnames=("shapes", "held", "expert", "rule", "std", "dtype"))
def _layer(seed, i, shapes, held, expert, rule, std, dtype):
    """One layer's base: ``shapes`` ((name, shape), ...) of the 2-D matrices,
    the held experts' stacks by expert index, and for a Gated DeltaNet layer
    (``rule`` = (value heads, d_conv, conv channels)) the rule's own."""
    key = jax.random.fold_in(_key(seed), 1 + i)
    out = {}
    for j, (name, shape) in enumerate(shapes):
        out[name] = _normal(jax.random.fold_in(key, j), shape, std, dtype)
    H, F = expert
    ekey = jax.random.fold_in(key, 1000)
    ids = jnp.asarray(held, jnp.uint32)
    for j, (name, shape) in enumerate((("eg", (H, F)), ("eu", (H, F)), ("ed", (F, H)))):
        out[name] = jax.vmap(lambda e, j=j, shape=shape: _normal(
            jax.random.fold_in(jax.random.fold_in(ekey, e), j), shape, std, dtype))(ids)
    if rule is not None:
        heads, K, ch = rule
        rkey = jax.random.fold_in(key, 2000)
        u = lambda j, shape, lo, hi: jax.random.uniform(  # noqa: E731
            jax.random.fold_in(rkey, j), shape, jnp.float32, lo, hi)
        out["alog"] = jnp.log(u(0, (heads,), 0.0, 16.0)).astype(dtype)
        dt = jnp.exp(u(1, (heads,), math.log(1e-3), math.log(1e-1)))
        out["dtb"] = (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
        bound = K ** -0.5
        out["conv"] = u(2, (K, ch), -bound, bound).astype(dtype)
    return out


def layer(sizes, seed, i):
    """Layer ``i``'s base on the device, flat names without the ``L<i>.``
    (the norms' weights included)."""
    d = dims(sizes)
    kind = d["kinds"][i]
    dtype = sizes["training"]["param_dtype"]
    rule = (d["Hv"], d["K"], d["conv"]) if kind == LINEAR else None
    out = _layer(jnp.uint32(int(seed) % 2 ** 32), jnp.uint32(i),
                 tuple(matrix_shapes(sizes, kind).items()), d["held"], (d["H"], d["F"]),
                 rule, float(sizes["initializer_range"]), dtype)
    starts = [("n1", d["H"], 0.0), ("n2", d["H"], 0.0)]
    if kind == LINEAR:
        starts.append(("gn", d["Dv"], 1.0))
    else:
        starts += [("qn", d["hd"], 0.0), ("kn", d["hd"], 0.0)]
    for name, n, v in starts:
        out[name] = jnp.full((n,), v, dtype)
    return out


@functools.partial(jax.jit, static_argnames=("V", "H", "std", "dtype"))
def _ends(seed, V, H, std, dtype):
    key = jax.random.fold_in(_key(seed), 0)
    return {"emb": _normal(jax.random.fold_in(key, 0), (V, H), std, dtype),
            "head": _normal(jax.random.fold_in(key, 1), (H, V), std, dtype),
            "norm": jnp.zeros((H,), dtype)}


def ends(sizes, seed):
    """The embedding, the head and the final norm on the device."""
    d = dims(sizes)
    return _ends(jnp.uint32(int(seed) % 2 ** 32), d["V"], d["H"],
                 float(sizes["initializer_range"]), sizes["training"]["param_dtype"])


@functools.partial(jax.jit, static_argnames=("shapes", "r", "std"))
def _adapters(seed, shapes, r, std):
    key = jax.random.fold_in(_key(seed), 0xADA)
    out = {}
    for j, (name, (fi, fo)) in enumerate(shapes):
        out[name + ".a"] = _normal(jax.random.fold_in(key, 2 * j), (fi, r), std, jnp.float32)
        out[name + ".b"] = _normal(jax.random.fold_in(key, 2 * j + 1), (r, fo), std, jnp.float32)
    return out


def adapters(sizes, seed):
    """The adapters' start, float32, on the device."""
    return _adapters(jnp.uint32(int(seed) % 2 ** 32), tuple(adapted_shapes(sizes).items()),
                     dims(sizes)["r"], float(sizes["initializer_range"]))


def make(sizes, seed):
    """Every array the program starts from: the base ON THE HOST (a layer at
    a time drawn on the device and fetched), the adapters on the device."""
    t0 = time.perf_counter()
    flat = {k: np.asarray(v) for k, v in ends(sizes, seed).items()}
    for i in range(dims(sizes)["L"]):
        for k, v in jax.device_get(layer(sizes, seed, i)).items():
            flat[f"L{i}.{k}"] = v
    flat.update(adapters(sizes, seed))
    print(f"[delta_moe] base of {sum(v.nbytes for v in flat.values()) / 1e9:.2f} GB drawn and "
          f"fetched in {time.perf_counter() - t0:.1f}s", file=sys.stderr, flush=True)
    return flat


def trained(flat):
    return {k: v for k, v in flat.items() if k.endswith((".a", ".b"))}


def to_program(flat, sizes):
    """``(adapters, frozen)`` as ``bcfl_tpu/models/delta_moe.py``'s flax names
    and ``models/lora.py``'s adapter keys."""
    d = dims(sizes)
    frozen = {"embed": {"embedding": flat["emb"]}, "final_norm": {"weight": flat["norm"]},
              "lm_head": {"kernel": flat["head"]}}
    adapters_ = {"lm_head": {"a": flat["lm.a"], "b": flat["lm.b"]}}
    for i, kind in enumerate(d["kinds"]):
        n = f"L{i}."
        layer_ = {"input_norm": {"weight": flat[n + "n1"]},
                  "post_attention_norm": {"weight": flat[n + "n2"]},
                  "moe": {"router": flat[n + "r"], "experts_gate": flat[n + "eg"],
                          "experts_up": flat[n + "eu"], "experts_down": flat[n + "ed"],
                          "shared_expert_gate": {"kernel": flat[n + "sgate"]},
                          "shared_experts": {}}}
        if kind == LINEAR:
            layer_["linear_attn"] = {"conv_kernel": flat[n + "conv"], "A_log": flat[n + "alog"],
                                     "dt_bias": flat[n + "dtb"], "norm": {"scale": flat[n + "gn"]},
                                     "in_proj_ba": {"kernel": flat[n + "ba"]}}
        else:
            layer_["self_attn"] = {"q_norm": {"weight": flat[n + "qn"]},
                                   "k_norm": {"weight": flat[n + "kn"]}}
        for m in ADAPTED[kind]:
            path = PROGRAM_NAMES[m]
            node = layer_
            for p in path[:-1]:
                node = node[p]
            node[path[-1]] = {"kernel": flat[n + m]}
            adapters_["/".join((f"layer_{i}",) + path)] = {
                "a": flat[n + m + ".a"], "b": flat[n + m + ".b"]}
        frozen[f"layer_{i}"] = layer_
    return adapters_, frozen


def from_program(trainable, sizes):
    out = {"lm.a": trainable["lm_head"]["a"], "lm.b": trainable["lm_head"]["b"]}
    for i, kind in enumerate(dims(sizes)["kinds"]):
        for m in ADAPTED[kind]:
            ab = trainable["/".join((f"layer_{i}",) + PROGRAM_NAMES[m])]
            out[f"L{i}.{m}.a"], out[f"L{i}.{m}.b"] = ab["a"], ab["b"]
    return out
