"""Kernel harness: one registry for every hand-written kernel (PERF.md
"Custom kernels").

The repo's first Pallas kernel (:mod:`bcfl_tpu.ops.pallas_flash`) grew its
own interpret-mode toggle, block-size clamping, impl dispatch, and parity
pinning; the second kernel (the codec, :mod:`bcfl_tpu.ops.pallas_codec`)
would have duplicated all four. This module extracts that machinery so a new
kernel is one :class:`KernelOp` registration away:

- **registry** — named ops, each with an XLA reference impl and an optional
  Pallas impl. Unknown names are rejected loudly (:func:`get_op`); an op
  WITHOUT a Pallas impl serves its XLA reference under every ``impl``
  request ("reject nothing": selection degrades, it never errors).
- **impl selection** (:func:`resolve`) — ``impl="xla" | "pallas" | "auto"``;
  ``auto`` = Pallas in a process that sees ONE TPU chip, XLA elsewhere
  (:func:`pallas_by_default`: Mosaic kernels cannot be partitioned
  automatically, so the GSPMD round programs of a multi-chip mesh are
  served by the references). An explicit
  ``"pallas"`` off-TPU runs the kernel body in interpret mode, so CI
  exercises the exact kernel everywhere (SURVEY.md §4's
  distributed-without-hardware strategy applied to kernels). The choice is
  made BEFORE the call, from the request, the backend and the op's static
  ``supports`` predicate over argument shapes: a kernel failure is an
  error, never a silent switch to the reference at call time.
- **one interpret-mode knob** (:func:`interpret_mode`) — kernels compile
  on a TPU and interpret everywhere else; ``BCFL_PALLAS_INTERPRET=1`` on
  a TPU is the one explicit debugging override, and asking for a compiled
  kernel (``=0``) off-TPU is an error.
- **block legalization** (:func:`legal_block` / :func:`legal_block_sizes`)
  — the (8, 128) Mosaic divisibility rule, generalized: real-TPU Mosaic
  requires the last two dims of every block to divide the dtype's
  (sublane, lane) tile — (8, 128) for f32 — or EQUAL the array dims
  (PERF.md documents this biting on silicon once already; interpret mode
  never checks it).
- **parity contract** — each op declares how closely the Pallas impl must
  match the XLA reference (``parity="bit-identical"`` or a pinned
  tolerance string). The contract is what tests pin and what
  ``scripts/kernel_bench.py`` verifies before it times anything.
- **microbench shapes** — each op may declare the real shapes it is paid
  at; ``scripts/kernel_bench.py`` sweeps exactly those rows.

Ops registered day one: ``flash_attention`` (:mod:`bcfl_tpu.ops.flash`,
tolerance parity — online-softmax reassociation) and the codec's
``int8_quantize`` / ``topk_select`` / ``int8_dequant`` / ``topk_scatter``
(:mod:`bcfl_tpu.ops.pallas_codec` via
:mod:`bcfl_tpu.compression.codecs`, bit-identical parity — ledger digests
chain over the encoded payload, so anything weaker would fork the chain).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Callable, Dict, Optional, Tuple

import jax

#: the one interpret-mode knob: unset = compiled Mosaic on a TPU, interpret
#: off-TPU (so CPU CI runs the exact kernel bodies). "1"/"true" forces
#: interpret mode on a TPU (kernel-body debugging on silicon hosts);
#: "0"/"false" off-TPU is an error — nothing there can compile a kernel.
INTERPRET_ENV = "BCFL_PALLAS_INTERPRET"

IMPLS = ("auto", "xla", "pallas")

#: f32 Mosaic tile: last two block dims must divide (8, 128) or equal the
#: array dims. (bf16 wants 16 sublanes, int8/fp8 32 — pass the unit that
#: covers every dtype a block touches.)
SUBLANES = 8
LANES = 128


def on_tpu() -> bool:
    """Is the default backend a TPU? Decides interpret mode."""
    return jax.default_backend() == "tpu"


def pallas_by_default() -> bool:
    """Does ``auto`` select the Pallas impls? On a TPU, in a process that
    sees exactly one device (a single chip, or a dist peer pinned to its
    own). With more, the round programs are GSPMD programs over a
    multi-chip mesh, and a ``pallas_call`` inside one is refused at
    lowering (v5e 2x2, jax 0.9.0: "NotImplementedError: Mosaic kernels
    cannot be automatically partitioned. Please wrap the call in a
    shard_map.") — so there ``auto`` serves the XLA references. An explicit
    ``"pallas"`` request still reaches the kernel, and fails loudly."""
    return on_tpu() and jax.device_count() == 1


def interpret_mode() -> bool:
    """Should Pallas kernels run in interpret mode? Compiled on a TPU,
    interpreted elsewhere (same kernel bodies on the CPU mesh).
    ``BCFL_PALLAS_INTERPRET=1`` forces interpret mode on a TPU; ``=0``
    off-TPU raises instead of silently interpreting."""
    val = os.environ.get(INTERPRET_ENV, "")
    if val == "":
        return not on_tpu()
    want = val.lower() not in ("0", "false", "no")
    if not want and not on_tpu():
        raise RuntimeError(
            f"{INTERPRET_ENV}={val} asks for compiled Pallas kernels, but "
            f"the backend is {jax.default_backend()!r}: Mosaic only "
            "compiles for a TPU (unset the variable to interpret)")
    return want


# ------------------------------------------------------------ block sizing


def legal_block(requested: int, dim: int, unit: int) -> int:
    """Clamp one requested block extent to what real-TPU Mosaic accepts:
    either a multiple of ``unit`` (the sublane/lane tile for that axis and
    dtype) or the whole array dim. A caller's odd block size becomes the
    nearest legal one instead of an obscure lowering error on silicon
    (generalized from ``pallas_flash._block_sizes``)."""
    b = min(requested, dim)
    if b == dim or b % unit == 0:
        return b
    b = (b // unit) * unit
    # floor hit zero: the nearest legal block is one tile — or the whole
    # (smaller-than-a-tile) dim, which is pad-free AND legal
    return b if b >= unit else min(unit, dim)


def legal_block_sizes(
        requests: Tuple[Tuple[int, int, int], ...]) -> Tuple[int, ...]:
    """Vector form: ``((requested, dim, unit), ...)`` -> legal extents."""
    return tuple(legal_block(b, d, u) for b, d, u in requests)


# ---------------------------------------------------------------- registry


@dataclasses.dataclass(frozen=True)
class KernelOp:
    """One named op: the XLA reference is the semantic ground truth; the
    Pallas impl must match it to ``parity``. ``bench_shapes`` are the
    real shapes the op is paid at (label -> args builder kwargs), swept by
    ``scripts/kernel_bench.py``."""

    name: str
    xla: Callable
    pallas: Optional[Callable] = None
    #: "bit-identical" or a pinned-tolerance note (e.g. "allclose:2e-2").
    #: Bit-identical ops may sit under wire digests; tolerance ops may not.
    parity: str = "bit-identical"
    #: static description of the microbench sweep, op-specific format
    bench_shapes: Tuple = ()
    #: static predicate over the call's ``(*args, **kwargs)`` (shapes and
    #: dtypes only — it runs at trace time): may the Pallas impl take this
    #: call? None = every call. Where it says no, :func:`select` serves the
    #: XLA reference and says so in the resolved impl name.
    supports: Optional[Callable[..., bool]] = None

    @property
    def has_pallas(self) -> bool:
        return self.pallas is not None


_REGISTRY: Dict[str, KernelOp] = {}


def register_op(op: KernelOp) -> KernelOp:
    """Register (idempotent per name+impls; a conflicting re-register is a
    programming error and fails loudly)."""
    prev = _REGISTRY.get(op.name)
    if prev is not None and prev is not op and (
            prev.xla is not op.xla or prev.pallas is not op.pallas):
        raise ValueError(f"kernel op {op.name!r} already registered with "
                         f"different impls")
    _REGISTRY[op.name] = op
    return op


def get_op(name: str) -> KernelOp:
    if name not in _REGISTRY:
        raise KeyError(
            f"unknown kernel op {name!r}; registered ops: "
            f"{sorted(_REGISTRY)} (register via "
            f"bcfl_tpu.ops.registry.register_op)")
    return _REGISTRY[name]


def list_ops() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def resolve(name: str, impl: str = "auto") -> Tuple[Callable, str]:
    """``(callable, resolved_impl)`` for an op under an impl request.

    ``auto`` = pallas iff the op has a Pallas impl AND
    :func:`pallas_by_default`; an explicit ``pallas`` request on an op with
    a Pallas impl runs it even off-TPU (interpret mode — how tier-1 pins
    kernel parity). An op without a Pallas impl serves its XLA reference
    under EVERY request: selection never errors, payloads never change."""
    op = get_op(name)
    if impl not in IMPLS:
        raise ValueError(f"unknown kernel impl {impl!r} for op {name!r} "
                         f"(one of {IMPLS})")
    if impl == "auto":
        impl = "pallas" if op.has_pallas and pallas_by_default() else "xla"
    if impl == "pallas" and not op.has_pallas:
        impl = "xla"
    return (op.pallas if impl == "pallas" else op.xla), impl


def select(name: str, impl: str, *args, **kwargs) -> Tuple[Callable, str]:
    """:func:`resolve` for one concrete call: the op's static ``supports``
    predicate sees the call's shapes, and a Pallas impl that does not take
    them is replaced by the XLA reference HERE, before anything runs. The
    resolved name then reads ``"xla(unsupported shape)"`` so a log can say
    which impl served which call."""
    fn, resolved = resolve(name, impl)
    op = get_op(name)
    if (resolved == "pallas" and op.supports is not None
            and not op.supports(*args, **kwargs)):
        return op.xla, "xla(unsupported shape)"
    return fn, resolved
