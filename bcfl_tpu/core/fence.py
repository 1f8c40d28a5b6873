"""The one device-completion seam.

``jax.block_until_ready`` is the fence. Checked on a TPU v5e (PERF.md
"Bring-up"): eight chained 4096^3 bf16 matmuls block for 6.4-6.5 ms, an
implied 169-172 TFLOP/s against the chip's 197 TFLOP/s peak, and the
scalar readback after it adds only its own transfer. Anything that
attributes wall time to a phase — StepClock spans, bench timing loops,
async-dispatch barriers — calls :func:`fence`, so the seam stays in one
place.
"""

from __future__ import annotations

import jax


def fence(tree) -> None:
    """Block the host until every array in ``tree`` is computed."""
    jax.block_until_ready(tree)
