"""Device-side parameter fingerprints for the ledger.

Round 3's ledger flow pulled the FULL stacked param tree to host every round
(``jax.device_get(stacked)`` + per-client SHA-256 over the raw bytes): for
BERT-base x 10 clients that is ~4.4 GB from device to host per round, and
it also forced round fusion off. Here the content digest is computed ON
DEVICE as a compact weighted fold and only ``[C, K]`` floats cross the
link; the SHA-256 chain then hashes those fingerprint bytes (plus a
structure digest over leaf names/dtypes/shapes, which needs no data
transfer).

Fingerprint construction (cheap by design — an earlier draft generated an
``O(params x K)`` random projection per call, whose threefry cost alone was
~90% of a small round's wall on CPU; this one is ~2 streaming passes over
the params and no per-element PRNG):

1. each leaf ``x`` is viewed as ``[C, M, LANES]`` (zero-padded to
   LANES=128, the TPU lane width),
2. folded over ``M`` with per-leaf cos/sin position weights
   ``cos(a*m + b), sin(a*m + b)``, where ``(a, b)`` derive from the SHA-256
   of the leaf's path name -> ``[C, 2*LANES]``,
3. all leaves' folds are summed and passed through ONE small fixed
   standard-normal projection ``[2*LANES, K]`` (generated once at trace
   time from a constant key) -> ``[C, K]``.

Any single element change moves the fingerprint (its lane picks up a
nonzero ``delta * w_m`` contribution that the dense projection spreads over
all K outputs); the position weights make value *moves* within a lane
detectable too. The construction is dtype-generic (every leaf is cast to
f32 before folding), which is what lets the engine fingerprint COMPRESSED
payload trees — int8 codes, f32 scales/values, int32 indices — so chain
auth covers the bytes actually on the wire (COMPRESSION.md §3; int32
indices above 2^24 can alias in the f32 cast, a cooperative-audit caveat of
the same class as the note below). Deterministic across calls and processes. This is a
*content* fingerprint for tamper-evidence in a cooperative audit chain, not
a cryptographic MAC over the raw bytes: an adversary who knows the
construction could craft a colliding tree, so faithful byte-hashing
(:func:`bcfl_tpu.ledger.ledger.params_digest`) remains available and is
what the engine uses when a tamper hook simulates in-flight modification of
host trees.

Cost: ~``3 * params`` flops per client per round, memory-bandwidth bound —
measured as a small fraction of round wall (``scripts/ledger_overhead.py``
-> ``results/ledger_overhead.json``).
"""

from __future__ import annotations

import hashlib
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from bcfl_tpu.metrics.tracing import scope

Tree = Any

K = 4  # fingerprint floats per client; 16 bytes of content evidence/entry
LANES = 128  # fold width (TPU lane count)


def _path_name(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "name", p)))
                    for p in path)


def _leaf_phase(name: str) -> tuple:
    """Per-leaf position-weight parameters (a, b), derived from the leaf's
    path name so sibling leaves fold differently."""
    h = hashlib.sha256(name.encode()).digest()
    a = 0.5 + int.from_bytes(h[:4], "little") % 100_000 / 100_000.0
    b = int.from_bytes(h[4:8], "little") % 628_318 / 100_000.0
    return a, b


def _projection(k: int) -> jnp.ndarray:
    """The one fixed [2*LANES, k] projection — tiny, constant key, generated
    at trace time (constant-folded by XLA)."""
    return jax.random.normal(jax.random.key(0xBCF1), (2 * LANES, k),
                             jnp.float32)


@scope("fingerprint")
def client_fingerprint(stacked: Tree, k: int = K) -> jnp.ndarray:
    """``[C, k]`` float32 fingerprint of a client-stacked tree (leaves
    ``[C, ...]``). Traceable — jit it once per structure; inside a scanned
    round body it adds a streaming fold per leaf."""
    flat = jax.tree_util.tree_flatten_with_path(stacked)[0]
    if not flat:
        raise ValueError("cannot fingerprint an empty tree")
    C = flat[0][1].shape[0]
    folds = jnp.zeros((C, 2 * LANES), jnp.float32)
    for path, leaf in flat:
        x = leaf.reshape(C, -1).astype(jnp.float32)
        n = x.shape[1]
        pad = (-n) % LANES
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)))
        m = (n + pad) // LANES
        x = x.reshape(C, m, LANES)
        a, b = _leaf_phase(_path_name(path))
        idx = jnp.arange(m, dtype=jnp.float32)
        w = jnp.stack([jnp.cos(a * idx + b), jnp.sin(a * idx + b)])  # [2, M]
        # tensordot, not einsum: measured 5x faster on the single-core CPU
        # lowering (2.4s vs 12.6s per 640M elements), same values
        y = jnp.tensordot(w, x, axes=((1,), (1,)))  # [2, C, LANES]
        folds = folds + y.transpose(1, 0, 2).reshape(C, 2 * LANES)
    return folds @ _projection(k)


def tree_fingerprint(tree: Tree, k: int = K) -> jnp.ndarray:
    """``[k]`` fingerprint of ONE client's (unstacked) tree — the faithful
    sequential mode's per-snapshot commit."""
    return client_fingerprint(
        jax.tree.map(lambda x: x[None], tree), k=k)[0]


def struct_digest(tree: Tree, use_native: bool = True) -> bytes:
    """SHA-256 over the tree's leaf names + dtypes + shapes — binds the
    fingerprint to the parameter STRUCTURE without touching leaf data (no
    device transfer; works on avals)."""
    from bcfl_tpu.ledger.ledger import _sha256_chunks

    chunks = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = _path_name(path)
        dt = jnp.dtype(leaf.dtype).str
        chunks.append(f"{name}:{dt}:{tuple(leaf.shape)}".encode())
    return _sha256_chunks(chunks, use_native)


def entry_digest(struct: bytes, fp_row: np.ndarray,
                 use_native: bool = True) -> bytes:
    """The 32-byte digest a fingerprint-mode ledger entry commits:
    ``SHA-256(struct_digest || fingerprint_bytes)``."""
    from bcfl_tpu.ledger.ledger import _sha256_chunks

    row = np.ascontiguousarray(np.asarray(fp_row, np.float32))
    return _sha256_chunks([struct, row.tobytes()], use_native)
