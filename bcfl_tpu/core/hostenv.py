"""Host-environment facts shared by every entry point: where the persistent
compile cache lives, the one table of device peaks, and the XLA:CPU
collective timeouts of the CPU-mesh drivers (scripts/). Nothing here
initializes a jax backend."""

from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: the checkout root (``<repo>/bcfl_tpu/core/hostenv.py`` -> ``<repo>``)
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
#: the one in-checkout cache location. The path is part of every cache key,
#: so it is fixed: never a tempdir, a pid or a timestamp.
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


#: the one peak table, keyed by ``jax.Device.device_kind``: bf16 matmul
#: FLOP/s per chip. Source: Google Cloud documentation, "TPU v5e" (197
#: TFLOP/s); "TPU v5 lite" is what jax reports for that chip. A kind that
#: is not here is an error, never a default: add the row with its source.
PEAK_BF16_FLOPS = {
    "TPU v5 lite": 197e12,
}


def device_peak_flops(device_kind: str) -> float:
    """bf16 peak FLOP/s of one chip of ``device_kind``; raises on a kind
    the table does not hold."""
    if device_kind not in PEAK_BF16_FLOPS:
        raise ValueError(
            f"device_kind {device_kind!r} is not in the peak table "
            f"(bcfl_tpu.core.hostenv.PEAK_BF16_FLOPS holds "
            f"{sorted(PEAK_BF16_FLOPS)}); add its row with a source")
    return PEAK_BF16_FLOPS[device_kind]


def compile_cache(default_dir: str = DEFAULT_CACHE_DIR) -> tuple:
    """Place jax's persistent compile cache; returns ``(dir, from_env)``.

    Call at the top of an entry point, before the first compile. With
    ``JAX_COMPILATION_CACHE_DIR`` set, NOTHING is set in code: jax reads
    the variable itself and subprocesses inherit it. Unset, the cache goes
    to ``default_dir`` and the variable is exported, so peer subprocesses
    (``dist.harness`` copies ``os.environ``) share the same entries."""
    env_dir = os.environ.get(CACHE_ENV)
    if env_dir:
        return env_dir, True
    os.environ[CACHE_ENV] = default_dir
    import jax

    # jax read its environment defaults at import; an already-imported jax
    # needs the value in its config too
    jax.config.update("jax_compilation_cache_dir", default_dir)
    return default_dir, False


def raise_cpu_collective_timeouts() -> None:
    """Raise XLA's CPU collective-rendezvous timeouts BEFORE backend init.

    On a CPU mesh the collective rendezvous aborts the whole process if any
    device thread lags >40s behind the others (rendezvous.cc terminate
    timeout) — easily hit on a shared/loaded 1-core host where 8 device
    threads compete through a multi-round scan. No-op if the caller already
    set the terminate flag (idempotent, and respects explicit tuning)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "collective_call_terminate" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_cpu_collective_call_warn_stuck_timeout_seconds=120"
            " --xla_cpu_collective_call_terminate_timeout_seconds=1200")
