"""Model registry.

The reference's model space is three HF checkpoints (SURVEY.md §2.1):
``albert-base-v2``, ``dmis-lab/biobert-v1.1`` (cased BERT-base), used via
``AutoModelForSequenceClassification``. Registry names map to
:class:`~bcfl_tpu.models.bert.EncoderConfig` instances; ``tiny-*`` variants are
the scale-down smoke models (the reference's de-facto test method is a
NUM_CLIENTS=2/NUM_ROUNDS=2 scale-down of the same script — SURVEY.md §4).

Four families: ``encoder`` (:mod:`bcfl_tpu.models.bert`), ``llama``
(:mod:`bcfl_tpu.models.llama`), ``latent_moe``
(:mod:`bcfl_tpu.models.latent_moe`: latent attention, an expert layer that
holds a share of the experts) and ``ssm_moe``
(:mod:`bcfl_tpu.models.ssm_moe`: state-space and attention layers in a
pattern, the same expert layer, a tied head). :func:`family_of` names a registry name's or a
built model's family; :func:`build`, :func:`lora_policy` and
:func:`tp_param_specs` dispatch on it and refuse what a family does not
support with the names that exist.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

from bcfl_tpu.models.bert import EncoderConfig, TextClassifier  # noqa: F401
from bcfl_tpu.models import lora  # noqa: F401

_CONFIGS: Dict[str, EncoderConfig] = {
    # test/bench scale-downs
    "tiny-bert": EncoderConfig(vocab_size=8192, hidden_size=128, num_layers=2,
                               num_heads=2, intermediate_size=512),
    "tiny-albert": EncoderConfig(vocab_size=8192, hidden_size=128, num_layers=2,
                                 num_heads=2, intermediate_size=512,
                                 share_layers=True, embedding_size=64),
    # mid-size encoder: real-data experiments on hosts without an
    # accelerator (a BERT-base run is TPU-sized); same family, 4 layers
    "small-bert": EncoderConfig(vocab_size=30522, hidden_size=512,
                                num_layers=4, num_heads=8,
                                intermediate_size=2048),
    # BERT-base family (BASELINE.json north-star model; biobert-v1.1 is a
    # cased BERT-base, vocab 28996 — reference server_IID_IMDB.py:48)
    "bert-base": EncoderConfig(vocab_size=30522, hidden_size=768, num_layers=12,
                               num_heads=12, intermediate_size=3072),
    "biobert-base": EncoderConfig(vocab_size=28996, hidden_size=768, num_layers=12,
                                  num_heads=12, intermediate_size=3072),
    # albert-base-v2 (reference serverless_NonIID_IMDB.py:30)
    "albert-base": EncoderConfig(vocab_size=30000, hidden_size=768, num_layers=12,
                                 num_heads=12, intermediate_size=3072,
                                 share_layers=True, embedding_size=128),
    # emilyalsentzer/Bio_ClinicalBERT — cased BERT-base init'd from BioBERT
    # (BASELINE.json configs[3] "ClinicalBERT Medical-Transcriptions")
    "clinical-bert": EncoderConfig(vocab_size=28996, hidden_size=768,
                                   num_layers=12, num_heads=12,
                                   intermediate_size=3072),
}

_LLAMA_CONFIGS: Dict[str, "LlamaConfig"] = {}


def _llama_configs():
    global _LLAMA_CONFIGS
    if not _LLAMA_CONFIGS:
        from bcfl_tpu.models.llama import LlamaConfig

        _LLAMA_CONFIGS = {
            # test/bench scale-down (GQA exercised: 4 heads / 2 kv heads)
            "tiny-llama": LlamaConfig(vocab_size=8192, hidden_size=128,
                                      num_layers=2, num_heads=4, num_kv_heads=2,
                                      intermediate_size=384, max_position=512),
            # Llama-2-7B (BASELINE.json configs[4]: LoRA fed fine-tune)
            "llama2-7b": LlamaConfig(vocab_size=32000, hidden_size=4096,
                                     num_layers=32, num_heads=32,
                                     intermediate_size=11008,
                                     max_position=4096),
        }
    return _LLAMA_CONFIGS


_LATENT_MOE_CONFIGS: Dict[str, "LatentMoEConfig"] = {}

# a name of a family with an expert layer may carry the cut this chip runs
# after an "@": "mistral-small-4@layers=8,experts_held=16" (n experts held:
# 0 .. n-1; of a pattern of layer kinds, the first ``layers``)
_CUTS = {"layers": "num_layers", "experts_held": "experts_held"}


def _latent_moe_configs():
    global _LATENT_MOE_CONFIGS
    if not _LATENT_MOE_CONFIGS:
        from bcfl_tpu.models.latent_moe import LatentMoEConfig

        _LATENT_MOE_CONFIGS = {
            # test scale-down: 4 heads of 16 + 16 / 32, latents 32 and 16,
            # 8 experts of width 32 with 2 a token, 1 shared
            "tiny-latent-moe": LatentMoEConfig(
                vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
                q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
                qk_rope_head_dim=16, v_head_dim=32, n_routed_experts=8,
                num_experts_per_tok=2, n_shared_experts=1,
                moe_intermediate_size=32, rope_original_max_position=64),
            # mistralai/Mistral-Small-4-119B-2603, the language model as
            # published (the dataclass's defaults): 36 layers, every expert
            "mistral-small-4": LatentMoEConfig(),
        }
    return _LATENT_MOE_CONFIGS


_SSM_MOE_CONFIGS: Dict[str, "SSMMoEConfig"] = {}


def _ssm_moe_configs():
    global _SSM_MOE_CONFIGS
    if not _SSM_MOE_CONFIGS:
        from bcfl_tpu.models.ssm_moe import SSMMoEConfig

        _SSM_MOE_CONFIGS = {
            # test scale-down: 3 mamba layers and 1 attention layer; 4 heads
            # of 8 with a state of 16 in chunks of 8; 4 query heads over 2
            # key-value heads; 8 experts of width 16 with 3 a token, a
            # shared MLP of 32
            "tiny-ssm-moe": SSMMoEConfig(
                vocab_size=512, hidden_size=32, num_layers=4,
                layer_types=("mamba", "mamba", "attention", "mamba"),
                num_heads=4, num_kv_heads=2, attention_multiplier=0.25,
                mamba_n_heads=8, mamba_d_head=8, mamba_d_state=16,
                mamba_chunk_size=8, num_local_experts=8,
                num_experts_per_tok=3, intermediate_size=16,
                shared_intermediate_size=32),
            # ibm-granite/granite-4.0-h-small as published (the dataclass's
            # defaults): 40 layers, every expert
            "granite-4.0-h-small": SSMMoEConfig(),
        }
    return _SSM_MOE_CONFIGS


def _split_cut(name: str):
    """``"base@k=v,..."`` -> ``(base, {config field: value})``."""
    base, _, cut = name.partition("@")
    out = {}
    for item in filter(None, cut.split(",")):
        k, _, v = item.partition("=")
        if k not in _CUTS:
            raise KeyError(f"unknown cut {k!r} in model name {name!r}; a "
                           f"name may carry {sorted(_CUTS)}")
        out[_CUTS[k]] = int(v)
    return base, out


FAMILIES = ("encoder", "llama", "latent_moe", "ssm_moe")
# the families with an LM head only, adapters on the activations and an
# expert layer that holds a share (one set of refusals, one LoRA policy)
_EXPERT_FAMILIES = ("latent_moe", "ssm_moe")


def family_of(model) -> str:
    """The family of a registry name or of a built model."""
    if isinstance(model, str):
        base = model.partition("@")[0]
        if model in _CONFIGS:
            return "encoder"
        if model in _llama_configs():
            return "llama"
        if base in _latent_moe_configs():
            return "latent_moe"
        if base in _ssm_moe_configs():
            return "ssm_moe"
        raise KeyError(f"unknown model {model!r}; have {list_models()}")
    name = type(model).__name__
    if isinstance(model, TextClassifier):
        return "encoder"
    if name in ("LlamaClassifier", "LlamaLM"):
        return "llama"
    if name == "LatentMoELM":
        return "latent_moe"
    if name == "SSMMoELM":
        return "ssm_moe"
    raise TypeError(f"{name} is a model of no family of the registry {FAMILIES}")


def get_config(name: str, **overrides):
    # encoder registry first: llama.py is only imported on an encoder miss,
    # so encoder-only runs never depend on the llama module importing
    if name in _CONFIGS:
        return dataclasses.replace(_CONFIGS[name], **overrides)
    family = family_of(name)
    if family == "llama":
        return dataclasses.replace(_llama_configs()[name], **overrides)
    base, cut = _split_cut(name)
    configs = _latent_moe_configs() if family == "latent_moe" else _ssm_moe_configs()
    return dataclasses.replace(configs[base], **{**cut, **overrides})


def list_models():
    return (sorted(_CONFIGS) + sorted(_llama_configs())
            + sorted(_latent_moe_configs()) + sorted(_ssm_moe_configs()))


def build(name: str, head: str = "classifier", **overrides):
    """Build the named model; the families share the forward signature
    ``apply(vars, ids, mask, deterministic=...) -> logits``. ``head="lm"``
    builds the causal-LM variant ([B, S, vocab] logits): the decoders only
    (encoders are bidirectional, so next-token training would leak the
    target), and the only head of the ``latent_moe`` and ``ssm_moe``
    families."""
    cfg = get_config(name, **overrides)
    family = family_of(name)
    if head not in ("classifier", "lm"):
        raise ValueError(f"unknown head {head!r}; have 'classifier' and 'lm'")
    if family == "encoder":
        if head == "lm":
            raise ValueError(
                f"model {name!r} is an encoder: causal-LM training needs a "
                "decoder (the llama, latent_moe and ssm_moe families)")
        return TextClassifier(cfg)
    if family == "llama":
        from bcfl_tpu.models.llama import LlamaClassifier, LlamaLM

        return LlamaLM(cfg) if head == "lm" else LlamaClassifier(cfg)
    if head != "lm":
        raise ValueError(
            f"model {name!r} (family {family}) has an LM head only: "
            "task='causal_lm'; classification heads exist for the encoder "
            "and llama families")
    if family == "latent_moe":
        from bcfl_tpu.models.latent_moe import LatentMoELM

        return LatentMoELM(cfg)
    from bcfl_tpu.models.ssm_moe import SSMMoELM

    return SSMMoELM(cfg)


@dataclasses.dataclass(frozen=True)
class LoRAPolicy:
    """How a family trains under LoRA (the one place that says so).

    ``targets``: the modules whose kernels carry an adapter.
    ``head_modules``: modules trained in full, substituted whole.
    ``adapter_dtype``: the adapters' stored type; None = the base's.
    ``on_activations``: the adapters enter the forward pass as ``x W + (x a)
    b`` through the model's ``lora`` collection (no merged kernel, no
    weight-gradient product of a frozen kernel); False = merged into the
    base, ``W + a b``, before the model runs (``fed.lora_merge``).
    ``tied``: ``((module, leaf path), ...)``, adapters of modules that have
    no kernel of their own and multiply by the TRANSPOSE of another module's
    leaf (a tied head, ``("lm_head", "embed/embedding")``): ``a`` [leaf's
    columns, r], ``b`` [r, leaf's rows]."""

    targets: tuple
    head_modules: tuple = lora.HEAD_MODULES
    adapter_dtype: Optional[str] = None
    on_activations: bool = False
    tied: tuple = ()


def lora_policy(model) -> LoRAPolicy:
    """The LoRA policy of a registry name's or a built model's family."""
    family = family_of(model)
    if family == "encoder":
        return LoRAPolicy(targets=lora.DEFAULT_TARGETS)
    if family == "llama":
        from bcfl_tpu.models.llama import LORA_TARGETS

        return LoRAPolicy(targets=LORA_TARGETS)
    # float32 adapters over a bfloat16 base: AdamW's step at a fine-tuning
    # learning rate is under half a bfloat16 rounding (PERF.md section 7)
    if family == "latent_moe":
        from bcfl_tpu.models.latent_moe import LORA_TARGETS

        return LoRAPolicy(targets=LORA_TARGETS, head_modules=(),
                          adapter_dtype="float32", on_activations=True)
    from bcfl_tpu.models.ssm_moe import LORA_TARGETS

    return LoRAPolicy(targets=LORA_TARGETS, head_modules=(),
                      adapter_dtype="float32", on_activations=True,
                      tied=(("lm_head", "embed/embedding"),))


def lora_targets(name: str):
    """Module names whose kernels get LoRA adapters, per model family."""
    return lora_policy(name).targets


def refusals(model: str, *, task: str, lora_rank: int, tp: int, sp: int,
             client_lora_ranks=None) -> Optional[str]:
    """Why this model cannot run under these settings, or None. Asked at
    config time (``FedConfig.__post_init__``), so that nothing a family
    lacks is silently replicated or skipped."""
    try:
        family = family_of(model)
    except KeyError:
        return None  # an unknown name is the registry's error, at build time
    if family not in _EXPERT_FAMILIES:
        return None
    why = []
    if task != "causal_lm":
        why.append(f"task={task!r}: the family has an LM head only "
                   "(task='causal_lm')")
    if lora_rank <= 0:
        why.append("lora_rank=0: the routed experts and the router are "
                   "frozen and the grouped product has no weight-gradient "
                   "pass; train adapters (lora_rank > 0)")
    if tp > 1:
        why.append(f"tp={tp}: no tensor-parallel layout for latent attention, "
                   "for a state-space mixer or for the expert layer (an "
                   "expert axis in core/mesh.py is not there yet)")
    if sp > 1:
        why.append(f"sp={sp}: ring attention is not wired into latent "
                   "attention, and no state-space scan carries its state "
                   "from chip to chip")
    if client_lora_ranks is not None and len(set(client_lora_ranks)) > 1:
        why.append("heterogeneous client_lora_ranks: rank clipping acts on "
                   "merged adapters, and this family's adapters enter on the "
                   "activations")
    if not why:
        return None
    return f"model {model!r} (family {family}) does not run with " + "; ".join(why)


def tp_param_specs(model, params, axis: str = "tp"):
    """Megatron tensor-parallel PartitionSpecs for ``params``, dispatched on
    the BUILT model's family. Pass the model INSTANCE (what :func:`build`
    returned), not a registry name: an ``hf_checkpoint`` run always builds an
    encoder even when the config names a llama model, and name-based specs
    would then match nothing and silently replicate the base onto every tp
    shard. This is the single dispatch point (the engine calls it too)."""
    if isinstance(model, str):
        raise TypeError(
            "tp_param_specs takes the built model instance, not a name: "
            "a name cannot see through hf_checkpoint overrides")
    family = family_of(model)
    if family == "encoder":
        from bcfl_tpu.models.bert import tp_specs

        return tp_specs(params, axis=axis)
    if family == "llama":
        from bcfl_tpu.models.llama import tp_specs

        return tp_specs(params, axis=axis)
    raise NotImplementedError(
        f"no tensor-parallel layout for the {family} family; the encoder and "
        "llama families have one")
