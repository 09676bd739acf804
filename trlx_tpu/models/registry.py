"""Model-family registry: ``model.model_type`` string -> architecture kit.

The reference hardwires architectures per trainer (`accelerate_ppo_model.py
:56-59` -> T5; `ilql_models.py:187` -> AutoModelForCausalLM). Here every
causal family (gpt2, gptj, gpt_neox) exposes one uniform kit — config class,
backbone module (same call interface), TP partition rules, KV-cache factory,
checkpoint loader — so trainers are family-agnostic; seq2seq (t5) has its
own trainer subclass.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Sequence, Tuple


@dataclass(frozen=True)
class ModelFamily:
    name: str
    config_cls: type
    backbone_cls: type
    partition_rules: Sequence
    init_cache: Callable  # (config, batch, capacity) -> cache
    load_checkpoint: Callable  # (path, dtype) -> (config, params)
    is_seq2seq: bool = False
    # has experts an `ep` mesh axis can shard (models/gpt2_moe.py,
    # models/olmoe.py); such a family sows router losses into `moe_losses`
    supports_ep: bool = False
    # matrices (by path substring) the family's programs consume at their
    # stored width, besides utils.ROLLOUT_CAST_EXCLUDE: a server's copy
    # leaves them as stored (utils.cast_is_exact)
    stored_width_leaves: Tuple[str, ...] = ()


_FAMILIES: Dict[str, ModelFamily] = {}


def register_model_family(family: ModelFamily, *aliases: str) -> ModelFamily:
    for key in (family.name, *aliases):
        _FAMILIES[key.lower()] = family
    return family


def get_model_family(name: str) -> ModelFamily:
    key = name.lower()
    if key not in _FAMILIES:
        _register_builtins()
    if key in _FAMILIES:
        return _FAMILIES[key]
    raise ValueError(
        f"Unknown model_type: {name!r}. Registered: {sorted(_FAMILIES)}"
    )


def hidden_size_of(config: Any) -> int:
    for attr in ("n_embd", "hidden_size", "d_model"):
        if hasattr(config, attr):
            return getattr(config, attr)
    raise ValueError(f"no hidden size on {type(config).__name__}")


def n_heads_of(config: Any) -> int:
    for attr in ("n_head", "num_heads", "num_attention_heads"):
        if hasattr(config, attr):
            return getattr(config, attr)
    raise ValueError(f"no head count on {type(config).__name__}")


def num_layers_of(config: Any) -> int:
    # order matters: T5 has both num_layers (encoder) and num_decoder_layers —
    # trainers freeze/branch on the decoder stack, so it takes precedence
    for attr in ("n_layer", "num_hidden_layers", "num_decoder_layers", "num_layers"):
        if hasattr(config, attr):
            return getattr(config, attr)
    raise ValueError(f"no layer count on {type(config).__name__}")


def _register_builtins() -> None:
    from trlx_tpu.models import conversion
    from trlx_tpu.models.gpt2 import GPT2Config, GPT2Model, PARTITION_RULES, init_cache
    from trlx_tpu.models.gptj import (
        GPTJConfig,
        GPTJModel,
        GPTJ_PARTITION_RULES,
        init_gptj_cache,
    )
    from trlx_tpu.models.gpt_neo import (
        GPTNeoConfig,
        GPTNeoModel,
        GPT_NEO_PARTITION_RULES,
        init_gpt_neo_cache,
    )
    from trlx_tpu.models.neox import (
        NeoXConfig,
        NeoXModel,
        NEOX_PARTITION_RULES,
        init_neox_cache,
    )
    from trlx_tpu.models.t5 import T5Config, T5Model, T5_PARTITION_RULES, init_t5_cache

    register_model_family(
        ModelFamily(
            "gpt2", GPT2Config, GPT2Model, PARTITION_RULES, init_cache,
            conversion.load_gpt2_checkpoint,
        )
    )
    register_model_family(
        ModelFamily(
            "gptj", GPTJConfig, GPTJModel, GPTJ_PARTITION_RULES, init_gptj_cache,
            conversion.load_gptj_checkpoint,
        ),
        "gpt-j",
    )
    register_model_family(
        ModelFamily(
            "gpt_neo", GPTNeoConfig, GPTNeoModel, GPT_NEO_PARTITION_RULES,
            init_gpt_neo_cache, conversion.load_gpt_neo_checkpoint,
        ),
        "gpt-neo",
    )
    register_model_family(
        ModelFamily(
            "gpt_neox", NeoXConfig, NeoXModel, NEOX_PARTITION_RULES, init_neox_cache,
            conversion.load_neox_checkpoint,
        ),
        "neox",
        "gpt-neox",
    )
    register_model_family(
        ModelFamily(
            "t5", T5Config, T5Model, T5_PARTITION_RULES, init_t5_cache,
            conversion.load_t5_checkpoint, is_seq2seq=True,
        ),
        "ul2",
    )
    from trlx_tpu.models.gpt2_moe import (
        GPT2MoEConfig,
        GPT2MoEModel,
        GPT2_MOE_PARTITION_RULES,
        _no_checkpoint,
    )

    register_model_family(
        ModelFamily(
            "gpt2_moe", GPT2MoEConfig, GPT2MoEModel, GPT2_MOE_PARTITION_RULES,
            init_cache, _no_checkpoint, supports_ep=True,
        ),
        "gpt2-moe",
    )
    from trlx_tpu.models.olmoe import (
        OLMOE_PARTITION_RULES,
        OlmoeConfig,
        OlmoeModel,
        init_olmoe_cache,
    )

    register_model_family(
        ModelFamily(
            "olmoe", OlmoeConfig, OlmoeModel, OLMOE_PARTITION_RULES,
            init_olmoe_cache, conversion.load_olmoe_checkpoint, supports_ep=True,
        )
    )
    from trlx_tpu.models.granite_hybrid import (
        GRANITE_HYBRID_PARTITION_RULES,
        GraniteMoeHybridConfig,
        GraniteMoeHybridModel,
        init_granite_hybrid_cache,
        no_granite_checkpoint,
    )

    register_model_family(
        ModelFamily(
            "granitemoehybrid", GraniteMoeHybridConfig, GraniteMoeHybridModel,
            GRANITE_HYBRID_PARTITION_RULES, init_granite_hybrid_cache,
            no_granite_checkpoint, supports_ep=True,
            # the state-space convolution's taps (ops/ssm.py multiplies at
            # f32), the shared expert's float32 output Dense, and the table:
            # the lookup is scaled at f32 and the tied head is
            # `nn.Embed.attend`, a float32 product on a float32 table
            stored_width_leaves=("conv_weight", "shared/down_proj", "wte"),
        )
    )
    from trlx_tpu.models.zaya import (
        ZAYA_PARTITION_RULES,
        ZayaConfig,
        ZayaModel,
        init_zaya_cache,
        no_zaya_checkpoint,
    )

    # supports_ep: it sows router losses like the other expert families; an
    # ep mesh itself is refused by name where the experts are built
    register_model_family(
        ModelFamily(
            "zaya", ZayaConfig, ZayaModel, ZAYA_PARTITION_RULES, init_zaya_cache,
            no_zaya_checkpoint, supports_ep=True,
            # CCA's taps and its [heads, Dh] bias table (ops/cca.py works at
            # f32) and the table of the tied `nn.Embed.attend` head
            stored_width_leaves=("conv0_weight", "conv1_bias", "wte"),
        )
    )
    from trlx_tpu.models.deepseek_v3 import (
        DEEPSEEK_V3_PARTITION_RULES,
        DeepseekV3Config,
        DeepseekV3Model,
        init_deepseek_v3_cache,
        no_deepseek_v3_checkpoint,
    )

    # not supports_ep: nothing trains its router (no loss is sown), and a
    # trainer refuses an ep axis for it by name
    register_model_family(
        ModelFamily(
            "deepseek_v3", DeepseekV3Config, DeepseekV3Model, DEEPSEEK_V3_PARTITION_RULES,
            init_deepseek_v3_cache, no_deepseek_v3_checkpoint,
        )
    )
    from trlx_tpu.models.qwen3_next import (
        QWEN3_NEXT_PARTITION_RULES,
        Qwen3NextConfig,
        Qwen3NextModel,
        init_qwen3_next_cache,
        no_qwen3_next_checkpoint,
    )

    # not supports_ep: nothing trains its router (no loss is sown), and the
    # model refuses an ep axis by name
    register_model_family(
        ModelFamily(
            "qwen3_next", Qwen3NextConfig, Qwen3NextModel, QWEN3_NEXT_PARTITION_RULES,
            init_qwen3_next_cache, no_qwen3_next_checkpoint,
            # the convolution's taps (ops/ssm.py::causal_conv multiplies at f32)
            stored_width_leaves=("conv_weight",),
        )
    )
    from trlx_tpu.models.ling import (
        LING_PARTITION_RULES,
        LingConfig,
        LingModel,
        init_ling_cache,
        no_ling_checkpoint,
    )

    # not supports_ep: nothing trains its router (no loss is sown), and the
    # model refuses an ep axis by name. `bailing_hybrid` is the model_type
    # the family's published config.json carries
    register_model_family(
        ModelFamily(
            "ling", LingConfig, LingModel, LING_PARTITION_RULES,
            init_ling_cache, no_ling_checkpoint,
            # the convolution's taps (ops/ssm.py::causal_conv multiplies at
            # f32); A_log, dt_bias, the norms and the selection bias are
            # vectors, which every server keeps as stored
            stored_width_leaves=("conv_weight",),
        ),
        "bailing_hybrid",
    )
    from trlx_tpu.models.nemotron_h import (
        NEMOTRON_H_PARTITION_RULES,
        NemotronHConfig,
        NemotronHModel,
        init_nemotron_h_cache,
        no_nemotron_h_checkpoint,
    )

    # not supports_ep: nothing trains its router (no loss is sown), and the
    # model refuses an ep axis by name
    register_model_family(
        ModelFamily(
            "nemotron_h", NemotronHConfig, NemotronHModel, NEMOTRON_H_PARTITION_RULES,
            init_nemotron_h_cache, no_nemotron_h_checkpoint,
            # the convolution's taps (ops/ssm.py::causal_conv multiplies at
            # f32); A_log, dt_bias, D, the norms and the selection bias are
            # vectors, which every server keeps as stored, and the router is
            # kept by utils.ROLLOUT_CAST_EXCLUDE
            stored_width_leaves=("conv_weight",),
        )
    )
