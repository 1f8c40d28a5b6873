"""Faults planted in the program under the decoder-under-LoRA cell (the
``prepare(engine)`` hook of ``harness.run_cell``)."""


def frozen_base_in_float32(engine):
    """The frozen base left in float32 where the configuration states
    bfloat16: the numbers barely move (the matrix units round the operands
    anyway), the memory doubles."""
    import jax
    import jax.numpy as jnp

    engine.frozen = jax.tree.map(lambda x: x.astype(jnp.float32), engine.frozen)


def adapter_not_applied(engine):
    """One adapter left out of the merge: it gets no gradient and stays
    where it started."""
    from bcfl_tpu.models import lora

    sound = lora.apply_lora

    def apply_lora(params, adapters, scale=1.0):
        rest = {k: v for k, v in adapters.items() if k != "model/layer_0/attention/q_proj"}
        return sound(params, rest, scale)

    lora.apply_lora = apply_lora
