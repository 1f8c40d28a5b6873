"""Weights from the seed for the state-space and attention hybrid decoder, a
layer at a time, and their layout as the program's trees.

Flat naming. The base (frozen, in ``training.param_dtype``):
  emb [V, H] (the head's kernel too: tied)   norm [H]
  L<i>.n1 / L<i>.n2 [H]
  a mamba layer:
    L<i>.in [H, 2 d_inner + 2 N + heads]   ([z | x B C | dt], as published)
    L<i>.conv [d_conv, d_inner + 2 N]   L<i>.convb [d_inner + 2 N]
    L<i>.alog / L<i>.D / L<i>.dtb [heads]   L<i>.gn [d_inner]
    L<i>.out [d_inner, H]
  an attention layer:
    L<i>.q / L<i>.o [H, heads * head] and back   L<i>.k / L<i>.v [H, kv_heads * head]
  every layer:
    L<i>.si [H, 2 Fs] ([gate | up], the published fused input_linear)   L<i>.so [Fs, H]
    L<i>.r [H, E]                                              (router)
    L<i>.ei [G, H, 2 F] ([gate | up])   L<i>.eo [G, F, H]   (the G experts HELD,
        in ascending order of their index; expert e's draw depends on e and
        not on which others are held)
What is trained, in float32: for each adapted matrix (a mamba layer's in and
out, an attention layer's q, k, v, o, every layer's si and so, and the head,
``lm``) the adapter ``m.a`` [fan_in, r] and ``m.b`` [r, fan_out]; the model
applies ``x m + (x m.a) m.b``. Both factors start from the seed, nonzero, so
both have a gradient in the first step.

Every matrix is normal(0, ``initializer_range``), every norm's scale and
``D`` 1. The recurrence's own parameters by Mamba-2's initialiser
(state-spaces/mamba, ``Mamba2.__init__``): ``A`` uniform in [1, 16] stored as
``log A``; ``dt`` log-uniform in [0.001, 0.1] stored as the value whose
softplus it is; the depthwise convolution's kernel and bias uniform in
+-1/sqrt(d_conv) (torch's Conv1d default, which Mamba-2 leaves). The base
never stands whole on the device here: ``make`` draws a layer on the device
and fetches it, and the reference draws a layer again when it reaches it
(``layer``).
"""

from __future__ import annotations

import functools
import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

WEIGHT_LANE = 0x53534D  # the weights' lane of the seed
ADAPTED = {"mamba": ("in", "out", "si", "so"), "attention": ("q", "k", "v", "o", "si", "so")}
PROGRAM_NAMES = {"in": ("mamba", "in_proj"), "out": ("mamba", "out_proj"),
                 "q": ("attention", "q_proj"), "k": ("attention", "k_proj"),
                 "v": ("attention", "v_proj"), "o": ("attention", "o_proj"),
                 "si": ("moe", "shared_experts", "input_linear"),
                 "so": ("moe", "shared_experts", "output_linear")}


def dims(sizes):
    """The sizes by short names, the cut applied."""
    held = held_experts(sizes)
    heads, hm, p = sizes["num_attention_heads"], sizes["mamba_n_heads"], sizes["mamba_d_head"]
    return dict(
        H=sizes["hidden_size"], L=sizes["layers"], V=sizes["vocab_rows"],
        kinds=tuple(sizes["layer_types"][:sizes["layers"]]),
        heads=heads, kv=sizes["num_key_value_heads"], hd=sizes["hidden_size"] // heads,
        Hm=hm, P=p, N=sizes["mamba_d_state"], K=sizes["mamba_d_conv"], di=hm * p,
        chunk=sizes["mamba_chunk_size"],
        E=sizes["num_local_experts"], k=sizes["num_experts_per_tok"],
        F=sizes["intermediate_size"], Fs=sizes["shared_intermediate_size"],
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
    mixer = ({"in": (H, 2 * d["di"] + 2 * d["N"] + d["Hm"]), "out": (d["di"], H)}
             if kind == "mamba" else
             {"q": (H, d["heads"] * d["hd"]), "k": (H, d["kv"] * d["hd"]),
              "v": (H, d["kv"] * d["hd"]), "o": (d["heads"] * d["hd"], H)})
    return {**mixer, "si": (H, 2 * d["Fs"]), "so": (d["Fs"], H), "r": (H, d["E"])}


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


@functools.partial(jax.jit, static_argnames=("shapes", "held", "expert", "mamba", "std", "dtype"))
def _layer(seed, i, shapes, held, expert, mamba, std, dtype):
    """One layer's base: ``shapes`` ((name, shape), ...) of the 2-D matrices,
    the held experts' stacks by expert index, and for a mamba layer
    (``mamba`` = (heads, d_conv, conv channels)) the recurrence's own."""
    key = jax.random.fold_in(_key(seed), 1 + i)
    out = {}
    for j, (name, shape) in enumerate(shapes):
        out[name] = _normal(jax.random.fold_in(key, j), shape, std, dtype)
    H, F = expert
    ekey = jax.random.fold_in(key, 1000)
    ids = jnp.asarray(held, jnp.uint32)
    for j, (name, shape) in enumerate((("ei", (H, 2 * F)), ("eo", (F, H)))):
        out[name] = jax.vmap(lambda e, j=j, shape=shape: _normal(
            jax.random.fold_in(jax.random.fold_in(ekey, e), j), shape, std, dtype))(ids)
    if mamba is not None:
        heads, K, ch = mamba
        mkey = jax.random.fold_in(key, 2000)
        u = lambda j, shape, lo, hi: jax.random.uniform(  # noqa: E731
            jax.random.fold_in(mkey, j), shape, jnp.float32, lo, hi)
        out["alog"] = jnp.log(u(0, (heads,), 1.0, 16.0)).astype(dtype)
        dt = jnp.exp(u(1, (heads,), math.log(1e-3), math.log(1e-1)))
        out["dtb"] = (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
        bound = K ** -0.5
        out["conv"] = u(2, (K, ch), -bound, bound).astype(dtype)
        out["convb"] = u(3, (ch,), -bound, bound).astype(dtype)
    return out


def layer(sizes, seed, i):
    """Layer ``i``'s base on the device, flat names without the ``L<i>.``
    (the norms' scales and ``D`` included)."""
    d = dims(sizes)
    kind = d["kinds"][i]
    dtype = sizes["training"]["param_dtype"]
    mamba = (d["Hm"], d["K"], d["di"] + 2 * d["N"]) if kind == "mamba" else None
    out = _layer(jnp.uint32(int(seed) % 2 ** 32), jnp.uint32(i),
                 tuple(matrix_shapes(sizes, kind).items()), d["held"], (d["H"], d["F"]),
                 mamba, float(sizes["initializer_range"]), dtype)
    ones = [("n1", d["H"]), ("n2", d["H"])]
    if kind == "mamba":
        ones += [("gn", d["di"]), ("D", d["Hm"])]
    for name, n in ones:
        out[name] = jnp.ones((n,), dtype)
    return out


@functools.partial(jax.jit, static_argnames=("V", "H", "std", "dtype"))
def _ends(seed, V, H, std, dtype):
    key = jax.random.fold_in(_key(seed), 0)
    return {"emb": _normal(jax.random.fold_in(key, 0), (V, H), std, dtype),
            "norm": jnp.ones((H,), dtype)}


def ends(sizes, seed):
    """The embedding (the head's kernel too) and the final norm on the device."""
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
    print(f"[ssm_moe] base of {sum(v.nbytes for v in flat.values()) / 1e9:.2f} GB drawn and "
          f"fetched in {time.perf_counter() - t0:.1f}s", file=sys.stderr, flush=True)
    return flat


def trained(flat):
    return {k: v for k, v in flat.items() if k.endswith((".a", ".b"))}


def to_program(flat, sizes):
    """``(adapters, frozen)`` as ``bcfl_tpu/models/ssm_moe.py``'s flax names
    and ``models/lora.py``'s adapter keys. The published fused
    ``input_linear`` of the routed experts is cut into the gate and up
    stacks the program's expert block takes."""
    d = dims(sizes)
    F = d["F"]
    frozen = {"embed": {"embedding": flat["emb"]}, "final_norm": {"scale": flat["norm"]}}
    adapters_ = {"lm_head": {"a": flat["lm.a"], "b": flat["lm.b"]}}
    for i, kind in enumerate(d["kinds"]):
        n = f"L{i}."
        layer_ = {"input_norm": {"scale": flat[n + "n1"]},
                  "post_attention_norm": {"scale": flat[n + "n2"]},
                  "moe": {"router": flat[n + "r"], "experts_gate": flat[n + "ei"][..., :F],
                          "experts_up": flat[n + "ei"][..., F:], "experts_down": flat[n + "eo"],
                          "shared_experts": {}}}
        if kind == "mamba":
            layer_["mamba"] = {"conv_kernel": flat[n + "conv"], "conv_bias": flat[n + "convb"],
                               "A_log": flat[n + "alog"], "D": flat[n + "D"],
                               "dt_bias": flat[n + "dtb"], "norm": {"scale": flat[n + "gn"]}}
        else:
            layer_["attention"] = {}
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
