"""In-graph communication codecs for client update deltas.

The paper's title promises *communication-efficient* P2P federated LLMs, but
until this module every round program exchanged full-precision update trees.
Here the quantity that crosses the simulated wire is a compressed encoding of
each client's **delta** (post-train params minus the round's reference
params, a quantity both endpoints can reconstruct against), with the
compression error carried forward in an **error-feedback residual** so it
never accumulates (Seide et al. 2014; Karimireddy et al. 2019 — the SNIPPETS
top-k/error-feedback exemplars implement the same scheme host-side; here it
is jit-compatible global-array math compiled INTO the GSPMD round programs).

Codecs (``CompressionConfig.kind``):

- ``int8`` — linear int8 quantization with per-chunk float32 scales
  (``chunk`` elements share one ``max|x|/127`` scale) and optional
  **stochastic rounding** (``floor(x/s + u)``, ``u ~ U[0,1)`` — unbiased, so
  quantization noise averages out across clients/rounds instead of biasing
  the aggregate). ~4x smaller than float32.
- ``topk`` — per-leaf magnitude top-k sparsification: keep the
  ``ceil(topk_frac * N)`` largest-|x| coordinates as (value, index) pairs.
  The dropped mass goes into the error-feedback residual and is transmitted
  in a later round once it grows large enough to make the cut.
- ``int8+topk`` — top-k first, then int8-quantize the surviving values:
  roughly ``(1 + 4) * k`` bytes per leaf vs ``4 * N`` raw.

All codec math is shape-static (chunk counts and k are Python ints derived
from leaf shapes at trace time), so a codec compiles into the round program
once and never retraces across rounds. Payload trees keep a leading global
client dim ``[C, ...]`` on every part, which makes them directly
fingerprintable by :func:`bcfl_tpu.ledger.fingerprint.client_fingerprint`
(the ledger chains digests of the COMPRESSED payload — auth covers what was
actually transmitted) and transport-corruptible by the fault plan
(:func:`corrupt_payload` perturbs the float parts; integer parts stay, so a
scheduled corruption is never silently widened into undefined int casts).

Bytes-on-wire accounting (:func:`payload_nbytes`) is host-side arithmetic
over leaf shapes — no device transfer — and feeds the per-round
``RoundRecord.bytes_on_wire`` metrics and the topology comms model.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from bcfl_tpu.metrics.tracing import scope
# importing pallas_codec registers the codec kernel ops (int8_quantize,
# topk_select, int8_dequant, topk_scatter) with the kernel harness
from bcfl_tpu.ops import pallas_codec  # noqa: F401
from bcfl_tpu.ops import registry

Tree = Any

KINDS = ("none", "int8", "topk", "int8+topk")

#: kernel impl selection for the codec hot loop (PERF.md "Custom kernels"):
#: "auto" = Pallas where one TPU chip is visible / XLA elsewhere
#: (registry.pallas_by_default), or force either. Every impl
#: produces byte-identical payloads (the registry's declared parity for
#: the codec ops), so this NEVER appears in :func:`wire_format`.
KERNEL_IMPLS = registry.IMPLS

# fold_in tag separating the codec's stochastic-rounding stream from the
# training dropout stream derived from the same per-round key
_CODEC_LANE = 0x51F7


@dataclasses.dataclass(frozen=True)
class CompressionConfig:
    """Build-time static codec parameters. Frozen/hashable so it lives inside
    :class:`bcfl_tpu.config.FedConfig` and keys the compiled-program cache
    (`fed.client_step._PROGRAM_CACHE`) — two configs that differ in any field
    get distinct round programs, never a silent cross-codec program reuse."""

    kind: str = "none"  # none | int8 | topk | int8+topk
    # int8: elements per quantization chunk (one f32 scale per chunk)
    chunk: int = 256
    # topk: fraction of each leaf's coordinates kept (>= 1 element per leaf)
    topk_frac: float = 0.05
    # unbiased stochastic rounding for int8 (deterministic per (round, seed))
    stochastic: bool = True
    # carry the per-client compression error into the next round's encode
    error_feedback: bool = True
    # codec kernel impl: "auto" (Pallas where one TPU chip is visible, XLA
    # elsewhere), "xla", or "pallas" (interpret mode off-TPU; refused at
    # lowering inside a multi-chip GSPMD program). Payload bytes are
    # identical under every value — deliberately NOT part of
    # wire_format(), so a resume may switch impls freely
    kernel_impl: str = "auto"

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(f"unknown compression kind {self.kind!r} "
                             f"(one of {KINDS})")
        if self.chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {self.chunk}")
        if not 0.0 < self.topk_frac <= 1.0:
            raise ValueError(
                f"topk_frac must be in (0, 1], got {self.topk_frac}")
        if self.kernel_impl not in KERNEL_IMPLS:
            raise ValueError(
                f"unknown kernel_impl {self.kernel_impl!r} "
                f"(one of {KERNEL_IMPLS})")

    @property
    def enabled(self) -> bool:
        return self.kind != "none"


# --------------------------------------------------------------------- leaves


def _path_name(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "name", p)))
                    for p in path)


def _leaf_k(comp: CompressionConfig, n: int) -> int:
    return max(1, int(math.ceil(comp.topk_frac * n)))


def _int8_parts(y: jnp.ndarray, chunk: int, key,
                stochastic: bool) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """[C, N] f32 -> (q int8 [C, M, chunk], scale f32 [C, M])."""
    C, N = y.shape
    pad = (-N) % chunk
    if pad:
        y = jnp.pad(y, ((0, 0), (0, pad)))
    M = (N + pad) // chunk
    y = y.reshape(C, M, chunk)
    scale = jnp.max(jnp.abs(y), axis=-1) / 127.0  # [C, M]
    z = y / jnp.maximum(scale, 1e-30)[..., None]
    if stochastic:
        # floor(z + u) is unbiased: E[q] = z for u ~ U[0, 1)
        z = jnp.floor(z + jax.random.uniform(key, z.shape))
    else:
        z = jnp.round(z)
    q = jnp.clip(z, -127.0, 127.0).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def _topk_parts(y: jnp.ndarray, k: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """[C, N] f32 -> (val f32 [C, k], idx int32 [C, k]) by |value|."""
    _, idx = jax.lax.top_k(jnp.abs(y), k)
    val = jnp.take_along_axis(y, idx, axis=1)
    return val, idx.astype(jnp.int32)


def _encode_leaf(comp: CompressionConfig, y: jnp.ndarray, key) -> dict:
    """[C, N] f32 -> payload part dict (all parts lead with C)."""
    n = y.shape[1]
    if comp.kind == "int8":
        q, s = _int8_parts(y, comp.chunk, key, comp.stochastic)
        return {"q": q, "s": s}
    if comp.kind == "topk":
        val, idx = _topk_parts(y, _leaf_k(comp, n))
        return {"v": val, "i": idx}
    if comp.kind == "int8+topk":
        k = _leaf_k(comp, n)
        val, idx = _topk_parts(y, k)
        q, s = _int8_parts(val, min(comp.chunk, k), key, comp.stochastic)
        return {"q": q, "s": s, "i": idx}
    raise ValueError(f"unknown compression kind {comp.kind!r}")


def _decode_leaf(comp: CompressionConfig, part: dict, n: int) -> jnp.ndarray:
    """payload part -> [C, N] f32. Decode selection goes through the same
    kernel registry (``int8_dequant`` / ``topk_scatter`` are registered
    XLA-only, so any ``kernel_impl`` degrades to the reference — "reject
    nothing")."""
    if comp.kind == "int8":
        return _run_op("int8_dequant", comp.kernel_impl,
                       part["q"], part["s"], n=n)
    if comp.kind == "topk":
        return _run_op("topk_scatter", comp.kernel_impl,
                       part["v"], part["i"], n=n)
    if comp.kind == "int8+topk":
        k = part["i"].shape[1]
        val = _run_op("int8_dequant", comp.kernel_impl,
                      part["q"], part["s"], n=k)
        return _run_op("topk_scatter", comp.kernel_impl,
                       val, part["i"], n=n)
    raise ValueError(f"unknown compression kind {comp.kind!r}")


# ---------------------------------------------------------------------- trees


def codec_key(stacked_keys) -> jax.Array:
    """Derive the codec's stochastic-rounding key from a round's stacked
    per-client training keys ([C] typed keys): one fold_in off client 0's
    key, on a lane the training stream never uses — deterministic per round,
    identical on the per-round and fused paths (both receive the same
    per-round key rows)."""
    return jax.random.fold_in(stacked_keys[0], _CODEC_LANE)


def encode_tree_unfused(comp: CompressionConfig, delta: Tree, key) -> dict:
    """Per-leaf reference encoder: one generic quantize/top-k lowering per
    leaf. Kept as the bit-identity oracle for the fused path below
    (tests/test_compression.py pins fused == unfused); the production
    entrypoint is :func:`encode_tree`."""
    flat = jax.tree_util.tree_flatten_with_path(delta)[0]
    if not flat:
        raise ValueError("cannot encode an empty tree")
    out = {}
    for i, (path, x) in enumerate(flat):
        C = x.shape[0]
        y = x.reshape(C, -1).astype(jnp.float32)
        out[_path_name(path)] = _encode_leaf(
            comp, y, jax.random.fold_in(key, i))
    return out


def _run_op(name: str, impl: str, *args, **kwargs):
    """Select a codec kernel op through the harness and run it. The choice
    is made before the call, from the impl request, the backend and the
    op's static shape predicate (a top-k row wider than the single-block
    VMEM budget is served by the XLA reference — the declared parity is
    bit-identical, so the wire never sees which impl ran). A kernel that
    fails after being selected is an error, not a fallback."""
    fn, _ = registry.select(name, impl, *args, **kwargs)
    return fn(*args, **kwargs)


def _int8_parts_batched(ys, keys, chunk: int, stochastic: bool,
                        impl: str = "xla"):
    """Fused int8 quantize over several [C, N_i] leaves sharing one chunk
    size: each leaf is padded to its chunk grid exactly as
    :func:`_int8_parts` would, the grids are CONCATENATED along the chunk
    axis, and the scale/divide/round/clip/cast pipeline runs ONCE over the
    union — per-chunk groupings (and the per-leaf stochastic-rounding
    uniforms, drawn under each leaf's own fold_in key) are unchanged, so
    the split-back parts are bit-identical to the per-leaf encode.

    The quantize pipeline itself runs through the kernel registry
    (``int8_quantize``: XLA reference or the fused-VMEM-pass Pallas kernel
    of :mod:`bcfl_tpu.ops.pallas_codec`, selected by ``impl``). The
    stochastic-rounding uniforms are ALWAYS drawn here, outside the
    kernel, under each leaf's own key — the kernel receives them as an
    operand, so impl selection never touches the draw stream.

    Returns [(q, scale)] in input order."""
    grids, Ms = [], []
    for y in ys:
        C, N = y.shape
        pad = (-N) % chunk
        if pad:
            y = jnp.pad(y, ((0, 0), (0, pad)))
        M = (N + pad) // chunk
        grids.append(y.reshape(C, M, chunk))
        Ms.append(M)
    g = jnp.concatenate(grids, axis=1)  # [C, sum(M), chunk]
    u = None
    if stochastic:
        # per-leaf uniforms under each leaf's own key (the identity with
        # the unfused path), concatenated along the same chunk axis
        u = jnp.concatenate(
            [jax.random.uniform(k, grid.shape)
             for k, grid in zip(keys, grids)], axis=1)
    q, scale = _run_op("int8_quantize", impl, g, u, stochastic=stochastic)
    out, off = [], 0
    for M in Ms:
        out.append((q[:, off:off + M], scale[:, off:off + M]
                    .astype(jnp.float32)))
        off += M
    return out


def _topk_parts_batched(ys, k: int, impl: str = "xla"):
    """Fused top-k over several [C, N] leaves of ONE flattened width:
    stacked to [L*C, N], a single magnitude-select sorts every row — the
    selection is row-independent, so each leaf's (val, idx) rows are
    bit-identical to its standalone call. The select runs through the
    kernel registry (``topk_select``: ``lax.top_k`` reference or the
    row-blocked Pallas kernel, which reproduces lax.top_k's tie-breaking
    exactly). Returns [(val, idx)] in input order."""
    L = len(ys)
    C, N = ys[0].shape
    stacked = jnp.concatenate(ys, axis=0)  # [L*C, N]
    val, idx = _run_op("topk_select", impl, stacked, k=k)
    return [(val[i * C:(i + 1) * C], idx[i * C:(i + 1) * C])
            for i in range(L)]


@scope("codec.encode")
def encode_tree(comp: CompressionConfig, delta: Tree, key) -> dict:
    """Stacked [C, ...] f32 delta tree -> payload dict keyed by leaf path.

    The payload is a plain pytree (dict of dicts of arrays), so it flows
    through jit/scan, shards on the client axis, fingerprints via
    ``client_fingerprint``, and device_gets like any other tree.

    FUSED dispatch (the comms hot path): instead of lowering one generic
    quantize / top-k per leaf, leaves are grouped — every leaf joins ONE
    concatenated int8 chunk-grid quantize, and leaves sharing a flattened
    width share ONE stacked ``lax.top_k`` (a transformer's N identical
    layers collapse to one call per distinct shape). The math is arranged
    so every per-leaf part is BIT-IDENTICAL to the per-leaf reference
    encode (:func:`encode_tree_unfused` — chunk groupings, per-leaf
    stochastic-rounding keys, and top-k row independence are all
    preserved), so ledger digests, wire frames, and checkpointed
    error-feedback state are unchanged. All shapes stay trace-time static:
    zero per-round retraces, pinned in tests/test_compression.py."""
    flat = jax.tree_util.tree_flatten_with_path(delta)[0]
    if not flat:
        raise ValueError("cannot encode an empty tree")
    paths, ys, keys = [], [], []
    for i, (path, x) in enumerate(flat):
        C = x.shape[0]
        paths.append(_path_name(path))
        ys.append(x.reshape(C, -1).astype(jnp.float32))
        keys.append(jax.random.fold_in(key, i))
    out: dict = {}
    if comp.kind in ("topk", "int8+topk"):
        # group by flattened width (same n => same k => stackable rows)
        by_n: dict = {}
        for i, y in enumerate(ys):
            by_n.setdefault(y.shape[1], []).append(i)
        vals = [None] * len(ys)
        idxs = [None] * len(ys)
        # sorted: group processing order must be a function of the leaf
        # WIDTHS, not of flatten insertion order — results land by leaf
        # index either way, but the trace/draw order stays host-invariant
        for n, group in sorted(by_n.items()):
            parts = _topk_parts_batched([ys[i] for i in group],
                                        _leaf_k(comp, n),
                                        impl=comp.kernel_impl)
            for i, (v, ix) in zip(group, parts):
                vals[i], idxs[i] = v, ix
        if comp.kind == "topk":
            for p, v, ix in zip(paths, vals, idxs):
                out[p] = {"v": v, "i": ix}
            return out
        # int8+topk: quantize the surviving values, fused per chunk size
        # min(chunk, k) — leaves sharing a width share a k, hence a grid
        by_ck: dict = {}
        for i, v in enumerate(vals):
            by_ck.setdefault(min(comp.chunk, v.shape[1]), []).append(i)
        for ck, group in sorted(by_ck.items()):  # same order contract
            parts = _int8_parts_batched(
                [vals[i] for i in group], [keys[i] for i in group],
                ck, comp.stochastic, impl=comp.kernel_impl)
            for i, (q, s) in zip(group, parts):
                out[paths[i]] = {"q": q, "s": s, "i": idxs[i]}
        return out
    if comp.kind == "int8":
        parts = _int8_parts_batched(ys, keys, comp.chunk, comp.stochastic,
                                    impl=comp.kernel_impl)
        for p, (q, s) in zip(paths, parts):
            out[p] = {"q": q, "s": s}
        return out
    raise ValueError(f"unknown compression kind {comp.kind!r}")


def kernel_plan(comp: CompressionConfig, template: Tree,
                num_clients: int) -> dict:
    """Which kernel impl :func:`encode_tree` selects for each leaf group,
    from shapes alone (no trace, no device): ``{op: {group: impl}}`` with
    the groups exactly as the fused encode forms them — top-k rows grouped
    by flattened width, int8 grids by chunk size. ``template`` is the
    unstacked trainable tree (shapes only are read)."""
    C = num_clients
    widths = [int(leaf.size) for leaf in jax.tree.leaves(template)]
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    plan: dict = {}
    int8_groups: dict = {}  # chunk -> total chunk-grid rows
    if comp.kind in ("topk", "int8+topk"):
        by_n: dict = {}
        for n in widths:
            by_n[n] = by_n.get(n, 0) + 1
        topk = plan["topk_select"] = {}
        for n, leaves in sorted(by_n.items()):
            k = _leaf_k(comp, n)
            _, topk[f"n={n} k={k} x{leaves}"] = registry.select(
                "topk_select", comp.kernel_impl, f32(leaves * C, n), k=k)
            if comp.kind == "int8+topk":
                ck = min(comp.chunk, k)
                int8_groups[ck] = int8_groups.get(ck, 0) + leaves * -(-k // ck)
    elif comp.kind == "int8":
        int8_groups[comp.chunk] = sum(-(-n // comp.chunk) for n in widths)
    if int8_groups:
        int8 = plan["int8_quantize"] = {}
        for ck, m in sorted(int8_groups.items()):
            g = f32(C, m, ck)
            _, int8[f"chunk={ck} M={m}"] = registry.select(
                "int8_quantize", comp.kernel_impl, g,
                g if comp.stochastic else None, stochastic=comp.stochastic)
    return plan


@scope("codec.decode")
def decode_tree(comp: CompressionConfig, payload: dict, like: Tree) -> Tree:
    """payload -> stacked f32 delta tree shaped like ``like`` ([C, ...])."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(like)
    leaves = []
    for path, x in flat:
        part = payload[_path_name(path)]
        C = x.shape[0]
        n = 1
        for d in x.shape[1:]:
            n *= d
        leaves.append(_decode_leaf(comp, part, n).reshape(x.shape))
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(like), leaves)


def roundtrip(comp: CompressionConfig, delta: Tree, resid: Tree,
              key) -> Tuple[dict, Tree, Tree]:
    """One wire exchange with error feedback: compensate the delta with the
    carried residual, encode, decode, and return what each side sees.

    Returns ``(payload, decoded, resid')`` — ``payload`` is what crosses the
    wire (and what the ledger fingerprints), ``decoded`` [C, ...] f32 is the
    receiver's reconstruction, ``resid' = (delta + resid) - decoded`` is the
    sender-side error the NEXT round's encode re-injects (zeros when
    ``error_feedback`` is off, so the carried state keeps one stable shape
    across both settings)."""
    if comp.error_feedback:
        comp_in = jax.tree.map(
            lambda d, r: d.astype(jnp.float32) + r, delta, resid)
    else:
        comp_in = jax.tree.map(lambda d: d.astype(jnp.float32), delta)
    payload = encode_tree(comp, comp_in, key)
    decoded = decode_tree(comp, payload, comp_in)
    if comp.error_feedback:
        resid = jax.tree.map(jnp.subtract, comp_in, decoded)
    else:
        resid = jax.tree.map(jnp.zeros_like, resid)
    return payload, decoded, resid


def zero_residual(trainable: Tree, num_clients: int) -> Tree:
    """Fresh [C, ...] f32 error-feedback state for an (unstacked) trainable
    template."""
    return jax.tree.map(
        lambda x: jnp.zeros((num_clients,) + x.shape, jnp.float32), trainable)


@scope("transport")
def corrupt_payload(payload: dict, scales: jnp.ndarray) -> dict:
    """Transport corruption of a compressed payload: add the per-client
    scale to every FLOAT part (quantization scales / top-k values). Integer
    parts (int8 codes, indices) are left alone — adding 1e6 through an int
    cast would be an undefined-overflow no-op rather than the fault plan's
    'exact float perturbation, never silent' contract. Every codec has at
    least one float part per leaf, so a scheduled corruption always lands
    (and always moves the payload fingerprint)."""
    return jax.tree.map(
        lambda x: x + scales.reshape(
            (-1,) + (1,) * (x.ndim - 1)).astype(x.dtype)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, payload)


def wire_format(comp: Optional["CompressionConfig"]) -> str:
    """Canonical identity string of the bytes this codec puts on the wire.

    Recorded in checkpoints (like the resolved PRNG impl name) so resume can
    REFUSE a codec change: a compressed run resumed under a different codec
    would silently re-inject the checkpointed error-feedback residual into
    the wrong encode (shapes match, semantics don't), and resuming
    uncompressed would silently drop the residual entirely.

    Only the fields the kind actually CONSUMES are part of the identity —
    a pure-topk run resumed with a different int8 chunk size has an
    unchanged encode, and refusing it would block a legitimate resume.
    ``kernel_impl`` is deliberately EXCLUDED: every impl's payload is
    byte-identical (the registry's bit-identical parity contract for the
    codec ops), so resuming a TPU run on CPU — or forcing the Pallas
    kernels mid-run — is always legitimate."""
    if comp is None or not comp.enabled:
        return "none"
    parts = [comp.kind]
    if comp.kind in ("int8", "int8+topk"):
        parts.append(f"chunk={comp.chunk}")
        parts.append(f"stochastic={int(comp.stochastic)}")
    if comp.kind in ("topk", "int8+topk"):
        parts.append(f"topk={comp.topk_frac}")
    parts.append(f"ef={int(comp.error_feedback)}")
    return ":".join(parts)


# ----------------------------------------------------------------- accounting


def payload_nbytes(comp: Optional[CompressionConfig], template: Tree) -> int:
    """Bytes ONE client ships per round for this codec, from leaf shapes
    alone (no device transfer). ``template`` is the unstacked trainable tree
    (or anything with its shapes/dtypes). ``None``/``kind='none'`` = the raw
    full-precision tree."""
    total = 0
    for leaf in jax.tree.leaves(template):
        n = int(leaf.size) if hasattr(leaf, "size") else 1
        if comp is None or not comp.enabled:
            total += n * jnp.dtype(leaf.dtype).itemsize
        elif comp.kind == "int8":
            m = -(-n // comp.chunk)  # ceil
            total += m * comp.chunk * 1 + m * 4
        elif comp.kind == "topk":
            total += _leaf_k(comp, n) * (4 + 4)
        elif comp.kind == "int8+topk":
            k = _leaf_k(comp, n)
            ck = min(comp.chunk, k)
            m = -(-k // ck)
            total += m * ck * 1 + m * 4 + k * 4
    return total
