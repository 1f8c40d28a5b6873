"""The flash kernels, the state-space scan in both its forms, and the expert and state-space
decoders' round programs at one layer,
compiled for a DESCRIBED TPU v5e, with no chip attached (the TPU's compiler
is installed here): what interpret mode cannot show, above all whether a
block request fits the kernel's scoped VMEM and which kernels the compiled
backward pass calls. Nothing runs, so nothing here is a time or a result.

The topology is described inside a fixture and these tests stay in this one
file: only one process may load the TPU's library, and under xdist every
worker imports every test file."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bcfl_tpu.ops import pallas_flash, registry


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def for_the_chip(monkeypatch):
    """Compiled kernels, not interpreted ones, and no persistent cache: an
    entry compiled for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    monkeypatch.setattr(registry, "interpret_mode", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile_grad(one_chip, S, D, dtype, block_q=None, block_k=None):
    q = jax.ShapeDtypeStruct((1, 2, S, D), dtype, sharding=one_chip)
    bias = jax.ShapeDtypeStruct((1, S), jnp.float32, sharding=one_chip)

    def loss(q, k, v, b):
        out = pallas_flash.flash_attention(q, k, v, b, True, block_q, block_k)
        return out.astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, (0, 1, 2, 3))).lower(q, q, q, bias).compile().as_text()
    assert text.count("tpu_custom_call") >= 3  # forward, dKV, dQ
    return text


@pytest.mark.parametrize("S,D,dtype", [
    (2048, 128, jnp.bfloat16),  # the expert decoder's cell
    (2048, 64, jnp.bfloat16),   # models/llama.py's heads
    (512, 64, jnp.bfloat16),    # a row under every request: one block a head
    (4096, 256, jnp.float32),   # a head the dQ request gives way to
], ids=["s2048-d128-bf16", "s2048-d64-bf16", "s512-d64-bf16", "s4096-d256-f32"])
def test_flash_kernels_compile_at_the_default_blocks(one_chip, for_the_chip, S, D, dtype):
    _compile_grad(one_chip, S, D, dtype)


def test_whole_row_blocks_compile_only_with_the_reckoned_vmem_request(
        one_chip, for_the_chip, monkeypatch):
    """2048 x 1024 score tiles need more scoped VMEM than Mosaic's default:
    with the request ``_blocks`` reckons they compile, without it (the
    default taken for boundless, so nothing is asked for) the compiler
    refuses them."""
    _compile_grad(one_chip, 4096, 128, jnp.bfloat16, 2048, 1024)
    monkeypatch.setattr(pallas_flash, "VMEM_DEFAULT_BYTES", 1 << 40)
    with pytest.raises(Exception, match="vmem"):
        _compile_grad(one_chip, 4096, 128, jnp.bfloat16, 2048, 1024)


def test_the_expert_decoders_round_program_runs_no_forward_kernel_twice(
        one_chip, for_the_chip, monkeypatch):
    """The fused round program the benchmark's expert-decoder cell dispatches
    (published widths, ONE layer, the cell's rows, ``remat``, LoRA r16,
    ``donate``), compiled for the described chip: a layer's kernels are the
    three flash kernels and the grouped products' three forward and five
    backward calls, so the backward pass runs no forward kernel again (12
    calls with the forward flash kernel retaken), and the compiler
    rematerialised nothing on its own to fit."""
    from bcfl_tpu.core.mesh import client_mesh
    from bcfl_tpu.fed.client_step import build_programs
    from bcfl_tpu.models import build, lora, lora_policy

    monkeypatch.setattr(registry, "pallas_by_default", lambda: True)
    monkeypatch.setattr(registry, "on_tpu", lambda: True)
    C, T, B, S, K = 2, 4, 2, 2048, 2
    model = build("mistral-small-4@layers=1,experts_held=16", head="lm", vocab_size=16384,
                  dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, remat=True, use_flash=True,
                  flash_min_seq=0)
    ids = jnp.ones((2, S), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.key(0), ids, ids)["params"]
    pol = lora_policy(model)
    adapters = jax.eval_shape(lambda p: lora.init_lora(
        jax.random.key(1), p, 16, targets=pol.targets, head_modules=pol.head_modules,
        dtype=pol.adapter_dtype), params)
    progs = build_programs(model, client_mesh(C, devices=list(one_chip.device_set)), optimizer="adamw",
                           learning_rate=1e-4, task="causal_lm", donate=True)

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)

    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    batches = {"ids": sds((C, T, B, S), jnp.int32), "mask": sds((C, T, B, S), jnp.int32),
               "example_mask": sds((C, T, B), jnp.float32)}
    per_round = sds((K, C), jnp.float32)
    compiled = progs.server_rounds_static_fp.lower(
        on_chip(adapters), on_chip(params), batches, per_round, sds((K, C, 2), jnp.uint32),
        per_round).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3 + 3 + 5
    assert ".remat" not in text
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 4e9  # 1.19 + 2.40 GB at one layer


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_state_space_scan_compiles_forward_and_backward(one_chip, for_the_chip, impl):
    """``ops/ssm_scan.py`` at the state-space cell's shape (2 clients x 1 row
    of 4096, 128 heads of 64, state 128, chunks of 256, bfloat16 operands),
    forward and backward, for the described chip; what is held beyond the
    arguments and results stays under 2 GB (a chunk's [2, 128, 256, 256]
    decays, not a row's). In ``jax.numpy``: one loop forward, the backward
    pass's two (the states carried forward again, the chunks last to
    first). As the Pallas kernels (``ops/pallas_ssm.py``): no loop, the
    forward kernel once and the backward pass's two (the states that enter
    the chunks, the chunks last to first), each within the VMEM it asks
    for, which the compiler checks."""
    from bcfl_tpu.ops import pallas_ssm
    from bcfl_tpu.ops.ssm_scan import ssm_scan

    B, S, H, P, N = 2, 4096, 128, 64, 128
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    args = (sds((B, S, H, P), jnp.bfloat16), sds((B, S, H), jnp.float32), sds((H,), jnp.float32),
            sds((B, S, N), jnp.bfloat16), sds((B, S, N), jnp.bfloat16), sds((H,), jnp.float32))
    assert registry.select("ssm_scan", impl, *args, chunk=256)[1] == impl
    scan = lambda *a: ssm_scan(*a, chunk=256, impl=impl)  # noqa: E731
    fwd = jax.jit(scan).lower(*args).compile()
    assert fwd.memory_analysis().temp_size_in_bytes < 1e9
    # a loss that reads y, so that the forward pass runs beside the backward
    grad = jax.jit(jax.grad(lambda *a: jnp.square(scan(*a).astype(jnp.float32)).sum(),
                            (0, 1, 3, 4))).lower(*args).compile()
    assert grad.memory_analysis().temp_size_in_bytes < 2e9
    if impl == "xla":
        assert fwd.as_text().count(" while(") == 1 and grad.as_text().count(" while(") == 3
        return
    assert fwd.as_text().count("tpu_custom_call") == 1 and " while(" not in fwd.as_text()
    assert grad.as_text().count("tpu_custom_call") == 3 and " while(" not in grad.as_text()
    hb, W = pallas_ssm._geometry(H, P)
    for kernel in ("fwd", "bwd"):
        assert pallas_ssm._vmem_bytes(kernel, 256, hb, P, N, H, W, 2) <= pallas_ssm.VMEM_MAX_BYTES


def test_one_mamba_layers_round_program_at_published_widths(one_chip, for_the_chip, monkeypatch):
    """The fused round program of the state-space cell at ONE layer (a
    Mamba-2 mixer and the expert layer at 18 of 72 held, published widths,
    the cell's rows, ``remat``, LoRA r16 with the tied head's adapter,
    ``donate``): it compiles for the described chip, its kernels are the
    grouped products' three forward and five backward calls (no forward
    kernel again) and the state-space scan's forward kernel and its backward
    pass's two (none of the forward's again), and the compiler rematerialised
    nothing on its own."""
    from bcfl_tpu.core.mesh import client_mesh
    from bcfl_tpu.fed.client_step import build_programs
    from bcfl_tpu.models import build, lora, lora_policy

    monkeypatch.setattr(registry, "pallas_by_default", lambda: True)
    monkeypatch.setattr(registry, "on_tpu", lambda: True)
    C, T, B, S, K = 2, 4, 1, 4096, 2
    model = build("granite-4.0-h-small@layers=1,experts_held=18", head="lm", vocab_size=25088,
                  dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, remat=True, use_flash=True,
                  flash_min_seq=0)
    ids = jnp.ones((2, S), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.key(0), ids, ids)["params"]
    pol = lora_policy(model)
    adapters = jax.eval_shape(lambda p: lora.init_lora(
        jax.random.key(1), p, 16, targets=pol.targets, head_modules=pol.head_modules,
        dtype=pol.adapter_dtype, tied=pol.tied), params)
    assert set(adapters) == {"lm_head", "layer_0/mamba/in_proj", "layer_0/mamba/out_proj",
                             "layer_0/moe/shared_experts/input_linear",
                             "layer_0/moe/shared_experts/output_linear"}
    progs = build_programs(model, client_mesh(C, devices=list(one_chip.device_set)), optimizer="adamw",
                           learning_rate=1e-4, task="causal_lm", donate=True)

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)

    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    batches = {"ids": sds((C, T, B, S), jnp.int32), "mask": sds((C, T, B, S), jnp.int32),
               "example_mask": sds((C, T, B), jnp.float32)}
    per_round = sds((K, C), jnp.float32)
    compiled = progs.server_rounds_static_fp.lower(
        on_chip(adapters), on_chip(params), batches, per_round, sds((K, C, 2), jnp.uint32),
        per_round).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3 + 5 + 1 + 2
    assert ".remat" not in text
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 6.5e9


def test_a_gated_deltanet_and_a_gated_attention_layers_round_program(one_chip, for_the_chip, monkeypatch):
    """The fused round program of the Gated DeltaNet cell at TWO layers (one
    Gated DeltaNet mixer with its chunked rule in ``jax.numpy``, one gated
    attention layer with heads of 256 through the flash kernels, each with
    the expert layer at 64 of 512 held; published widths, the cell's rows of
    8192, ``remat``, LoRA r16, ``donate``): it compiles for the described
    chip, its kernels are the flash kernels' three and the grouped products'
    three forward and five backward calls a layer (no forward kernel again),
    and the compiler rematerialised nothing on its own."""
    from bcfl_tpu.core.mesh import client_mesh
    from bcfl_tpu.fed.client_step import build_programs
    from bcfl_tpu.models import build, lora, lora_policy
    from bcfl_tpu.models.delta_moe import FULL, LINEAR

    monkeypatch.setattr(registry, "pallas_by_default", lambda: True)
    monkeypatch.setattr(registry, "on_tpu", lambda: True)
    C, T, B, S, K = 2, 4, 1, 8192, 2
    model = build("qwen3-next-80b-a3b@layers=2,experts_held=64", head="lm", vocab_size=18992,
                  layer_types=(LINEAR, FULL), dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                  remat=True, use_flash=True, flash_min_seq=0)
    ids = jnp.ones((2, S), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.key(0), ids, ids)["params"]
    pol = lora_policy(model)
    adapters = jax.eval_shape(lambda p: lora.init_lora(
        jax.random.key(1), p, 16, targets=pol.targets, head_modules=pol.head_modules,
        dtype=pol.adapter_dtype, tied=pol.tied), params)
    assert len(adapters) == 5 + 7 + 1
    progs = build_programs(model, client_mesh(C, devices=list(one_chip.device_set)), optimizer="adamw",
                           learning_rate=1e-4, task="causal_lm", donate=True)

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)

    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    batches = {"ids": sds((C, T, B, S), jnp.int32), "mask": sds((C, T, B, S), jnp.int32),
               "example_mask": sds((C, T, B), jnp.float32)}
    per_round = sds((K, C), jnp.float32)
    compiled = progs.server_rounds_static_fp.lower(
        on_chip(adapters), on_chip(params), batches, per_round, sds((K, C, 2), jnp.uint32),
        per_round).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3 + 2 * (3 + 5)
    assert ".remat" not in text
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 9e9
