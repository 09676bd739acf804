"""HF checkpoint -> JAX param-pytree conversion.

The reference consumes HF torch checkpoints directly
(``AutoModelForCausalLM.from_pretrained``, `ppo_models.py:233`;
``AutoModelForSeq2SeqLM`` bf16, `ppo_models.py:610-615`). The TPU framework
implements the architectures natively, so checkpoints are converted once,
host-side, into the flax param tree. Conversion is validated by exact-logit
parity tests against torch CPU forward (``tests/test_gpt2_parity.py``) —
SURVEY §7.3 lists this as a hard part.

GPT-2 note: HF ``Conv1D`` stores weights as (in_features, out_features),
identical to flax ``Dense`` kernels — no transposes anywhere in the GPT-2 map.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, Mapping, Optional

import jax.numpy as jnp
import numpy as np

from trlx_tpu.models.gpt2 import GPT2Config


def _np(t) -> np.ndarray:
    """torch tensor / array-like -> numpy (host)."""
    if hasattr(t, "detach"):
        t = t.detach()
    if hasattr(t, "float"):
        # bf16 torch tensors can't go straight to numpy
        t = t.float()
    if hasattr(t, "cpu"):
        t = t.cpu()
    if hasattr(t, "numpy"):
        return t.numpy()
    return np.asarray(t)


def gpt2_config_from_hf(path_or_dict) -> GPT2Config:
    """Read an HF ``config.json`` (path or dict) into :class:`GPT2Config`."""
    if isinstance(path_or_dict, (str, os.PathLike)):
        with open(os.path.join(path_or_dict, "config.json")) as f:
            d = json.load(f)
    elif hasattr(path_or_dict, "to_dict"):
        d = path_or_dict.to_dict()
    else:
        d = dict(path_or_dict)
    return GPT2Config(
        vocab_size=d["vocab_size"],
        n_positions=d.get("n_positions", 1024),
        n_embd=d["n_embd"],
        n_layer=d["n_layer"],
        n_head=d["n_head"],
        layer_norm_epsilon=d.get("layer_norm_epsilon", 1e-5),
    )


def convert_gpt2_state_dict(
    state_dict: Mapping[str, Any], config: GPT2Config, dtype: str = "float32"
) -> Dict[str, Any]:
    """HF ``GPT2LMHeadModel`` state dict -> ``GPT2Model`` param tree.

    Accepts keys with or without the ``transformer.`` prefix. The LM head is
    tied to ``wte`` in both frameworks, so only the transformer is mapped.
    """
    sd = {k.removeprefix("transformer."): v for k, v in state_dict.items()}
    cast = lambda t: jnp.asarray(_np(t), dtype=jnp.dtype(dtype))

    params: Dict[str, Any] = {
        "wte": {"embedding": cast(sd["wte.weight"])},
        "wpe": {"embedding": cast(sd["wpe.weight"])},
        "ln_f": {"scale": cast(sd["ln_f.weight"]), "bias": cast(sd["ln_f.bias"])},
    }
    for i in range(config.n_layer):
        p = f"h.{i}."
        params[f"h_{i}"] = {
            "ln_1": {"scale": cast(sd[p + "ln_1.weight"]), "bias": cast(sd[p + "ln_1.bias"])},
            "ln_2": {"scale": cast(sd[p + "ln_2.weight"]), "bias": cast(sd[p + "ln_2.bias"])},
            "attn": {
                "c_attn": {
                    "kernel": cast(sd[p + "attn.c_attn.weight"]),
                    "bias": cast(sd[p + "attn.c_attn.bias"]),
                },
                "c_proj": {
                    "kernel": cast(sd[p + "attn.c_proj.weight"]),
                    "bias": cast(sd[p + "attn.c_proj.bias"]),
                },
            },
            "mlp": {
                "c_fc": {
                    "kernel": cast(sd[p + "mlp.c_fc.weight"]),
                    "bias": cast(sd[p + "mlp.c_fc.bias"]),
                },
                "c_proj": {
                    "kernel": cast(sd[p + "mlp.c_proj.weight"]),
                    "bias": cast(sd[p + "mlp.c_proj.bias"]),
                },
            },
        }
    return params


def t5_config_from_hf(path_or_dict) -> "T5Config":
    """Read an HF T5/UL2 ``config.json`` into :class:`T5Config`."""
    from trlx_tpu.models.t5 import T5Config

    if isinstance(path_or_dict, (str, os.PathLike)):
        with open(os.path.join(path_or_dict, "config.json")) as f:
            d = json.load(f)
    elif hasattr(path_or_dict, "to_dict"):
        d = path_or_dict.to_dict()
    else:
        d = dict(path_or_dict)
    return T5Config(
        vocab_size=d["vocab_size"],
        d_model=d["d_model"],
        d_kv=d["d_kv"],
        d_ff=d["d_ff"],
        num_layers=d["num_layers"],
        num_decoder_layers=d.get("num_decoder_layers", d["num_layers"]),
        num_heads=d["num_heads"],
        relative_attention_num_buckets=d.get("relative_attention_num_buckets", 32),
        relative_attention_max_distance=d.get("relative_attention_max_distance", 128),
        layer_norm_epsilon=d.get("layer_norm_epsilon", 1e-6),
        feed_forward_proj=d.get("feed_forward_proj", "relu"),
        tie_word_embeddings=d.get("tie_word_embeddings", True),
        decoder_start_token_id=d.get("decoder_start_token_id", 0) or 0,
    )


def convert_t5_state_dict(
    state_dict: Mapping[str, Any], config, dtype: str = "float32"
) -> Dict[str, Any]:
    """HF ``T5ForConditionalGeneration`` state dict -> ``T5Model`` param tree.

    torch ``nn.Linear`` stores (out, in); flax Dense wants (in, out) — every
    projection kernel transposes. HF parameterizes the relative attention
    bias inside block 0 of each stack and reuses it downstream; here it maps
    to the stack-level ``enc_rel_bias``/``dec_rel_bias`` modules.
    """
    sd = dict(state_dict)
    cast = lambda t: jnp.asarray(_np(t), dtype=jnp.dtype(dtype))
    castT = lambda t: jnp.asarray(_np(t).T.copy(), dtype=jnp.dtype(dtype))

    def attn(prefix: str) -> Dict[str, Any]:
        return {
            "q": {"kernel": castT(sd[prefix + ".q.weight"])},
            "k": {"kernel": castT(sd[prefix + ".k.weight"])},
            "v": {"kernel": castT(sd[prefix + ".v.weight"])},
            "o": {"kernel": castT(sd[prefix + ".o.weight"])},
        }

    def ff(prefix: str) -> Dict[str, Any]:
        if config.is_gated_act:
            return {
                "wi_0": {"kernel": castT(sd[prefix + ".wi_0.weight"])},
                "wi_1": {"kernel": castT(sd[prefix + ".wi_1.weight"])},
                "wo": {"kernel": castT(sd[prefix + ".wo.weight"])},
            }
        return {
            "wi": {"kernel": castT(sd[prefix + ".wi.weight"])},
            "wo": {"kernel": castT(sd[prefix + ".wo.weight"])},
        }

    params: Dict[str, Any] = {
        "shared": {"embedding": cast(sd["shared.weight"])},
        "enc_rel_bias": {
            "relative_attention_bias": {
                "embedding": cast(
                    sd["encoder.block.0.layer.0.SelfAttention.relative_attention_bias.weight"]
                )
            }
        },
        "dec_rel_bias": {
            "relative_attention_bias": {
                "embedding": cast(
                    sd["decoder.block.0.layer.0.SelfAttention.relative_attention_bias.weight"]
                )
            }
        },
        "enc_final_ln": {"weight": cast(sd["encoder.final_layer_norm.weight"])},
        "dec_final_ln": {"weight": cast(sd["decoder.final_layer_norm.weight"])},
    }
    if not config.tie_word_embeddings:
        params["lm_head"] = {"kernel": castT(sd["lm_head.weight"])}

    for i in range(config.num_layers):
        p = f"encoder.block.{i}."
        params[f"enc_{i}"] = {
            "SelfAttention": attn(p + "layer.0.SelfAttention"),
            "ln_self": {"weight": cast(sd[p + "layer.0.layer_norm.weight"])},
            "DenseReluDense": ff(p + "layer.1.DenseReluDense"),
            "ln_ff": {"weight": cast(sd[p + "layer.1.layer_norm.weight"])},
        }
    for i in range(config.num_decoder_layers):
        p = f"decoder.block.{i}."
        params[f"dec_{i}"] = {
            "SelfAttention": attn(p + "layer.0.SelfAttention"),
            "ln_self": {"weight": cast(sd[p + "layer.0.layer_norm.weight"])},
            "EncDecAttention": attn(p + "layer.1.EncDecAttention"),
            "ln_cross": {"weight": cast(sd[p + "layer.1.layer_norm.weight"])},
            "DenseReluDense": ff(p + "layer.2.DenseReluDense"),
            "ln_ff": {"weight": cast(sd[p + "layer.2.layer_norm.weight"])},
        }
    return params


def load_t5_checkpoint(model_path: str, dtype: str = "float32"):
    """Load an on-disk HF T5/UL2 checkpoint -> (T5Config, param tree).

    The fork loads its checkpoint in bf16 (`ppo_models.py:610-615`); here
    param dtype is configurable (bf16 compute is set by the arch config).
    """
    from transformers import AutoModelForSeq2SeqLM

    model = AutoModelForSeq2SeqLM.from_pretrained(model_path, local_files_only=True)
    config = t5_config_from_hf(model.config)
    params = convert_t5_state_dict(model.state_dict(), config, dtype)
    return config, params


def gptj_config_from_hf(path_or_dict) -> "GPTJConfig":
    from trlx_tpu.models.gptj import GPTJConfig

    if isinstance(path_or_dict, (str, os.PathLike)):
        with open(os.path.join(path_or_dict, "config.json")) as f:
            d = json.load(f)
    elif hasattr(path_or_dict, "to_dict"):
        d = path_or_dict.to_dict()
    else:
        d = dict(path_or_dict)
    return GPTJConfig(
        vocab_size=d["vocab_size"],
        n_positions=d.get("n_positions", 2048),
        n_embd=d["n_embd"],
        n_layer=d["n_layer"],
        n_head=d["n_head"],
        rotary_dim=d.get("rotary_dim") or (d["n_embd"] // d["n_head"]),
        layer_norm_epsilon=d.get("layer_norm_epsilon", 1e-5),
    )


def convert_gptj_state_dict(
    state_dict: Mapping[str, Any], config, dtype: str = "float32"
) -> Dict[str, Any]:
    """HF ``GPTJForCausalLM`` -> ``GPTJModel`` params (Linear kernels
    transpose; lm_head is untied with bias)."""
    sd = {k.removeprefix("transformer."): v for k, v in state_dict.items()}
    cast = lambda t: jnp.asarray(_np(t), dtype=jnp.dtype(dtype))
    castT = lambda t: jnp.asarray(_np(t).T.copy(), dtype=jnp.dtype(dtype))

    params: Dict[str, Any] = {
        "wte": {"embedding": cast(sd["wte.weight"])},
        "ln_f": {"scale": cast(sd["ln_f.weight"]), "bias": cast(sd["ln_f.bias"])},
        "lm_head": {
            "kernel": castT(sd["lm_head.weight"]),
            "bias": cast(sd["lm_head.bias"]),
        },
    }
    for i in range(config.n_layer):
        p = f"h.{i}."
        params[f"h_{i}"] = {
            "ln_1": {"scale": cast(sd[p + "ln_1.weight"]), "bias": cast(sd[p + "ln_1.bias"])},
            "attn": {
                "q_proj": {"kernel": castT(sd[p + "attn.q_proj.weight"])},
                "k_proj": {"kernel": castT(sd[p + "attn.k_proj.weight"])},
                "v_proj": {"kernel": castT(sd[p + "attn.v_proj.weight"])},
                "out_proj": {"kernel": castT(sd[p + "attn.out_proj.weight"])},
            },
            "mlp": {
                "fc_in": {
                    "kernel": castT(sd[p + "mlp.fc_in.weight"]),
                    "bias": cast(sd[p + "mlp.fc_in.bias"]),
                },
                "fc_out": {
                    "kernel": castT(sd[p + "mlp.fc_out.weight"]),
                    "bias": cast(sd[p + "mlp.fc_out.bias"]),
                },
            },
        }
    return params


def load_gptj_checkpoint(model_path: str, dtype: str = "float32"):
    from transformers import AutoModelForCausalLM

    model = AutoModelForCausalLM.from_pretrained(model_path, local_files_only=True)
    config = gptj_config_from_hf(model.config)
    return config, convert_gptj_state_dict(model.state_dict(), config, dtype)


def neox_config_from_hf(path_or_dict) -> "NeoXConfig":
    from trlx_tpu.models.neox import NeoXConfig

    if isinstance(path_or_dict, (str, os.PathLike)):
        with open(os.path.join(path_or_dict, "config.json")) as f:
            d = json.load(f)
    elif hasattr(path_or_dict, "to_dict"):
        d = path_or_dict.to_dict()
    else:
        d = dict(path_or_dict)
    return NeoXConfig(
        vocab_size=d["vocab_size"],
        max_position_embeddings=d.get("max_position_embeddings", 2048),
        hidden_size=d["hidden_size"],
        num_hidden_layers=d["num_hidden_layers"],
        num_attention_heads=d["num_attention_heads"],
        rotary_pct=d.get("rotary_pct", 0.25),
        rotary_emb_base=d.get("rotary_emb_base", 10000.0),
        use_parallel_residual=d.get("use_parallel_residual", True),
        layer_norm_eps=d.get("layer_norm_eps", 1e-5),
    )


def convert_neox_state_dict(
    state_dict: Mapping[str, Any], config, dtype: str = "float32"
) -> Dict[str, Any]:
    """HF ``GPTNeoXForCausalLM`` -> ``NeoXModel`` params. The fused QKV
    kernel keeps HF's head-major [H, 3*Dh] output layout (transpose only)."""
    sd = {k.removeprefix("gpt_neox."): v for k, v in state_dict.items()}
    cast = lambda t: jnp.asarray(_np(t), dtype=jnp.dtype(dtype))
    castT = lambda t: jnp.asarray(_np(t).T.copy(), dtype=jnp.dtype(dtype))

    params: Dict[str, Any] = {
        "wte": {"embedding": cast(sd["embed_in.weight"])},
        "ln_f": {
            "scale": cast(sd["final_layer_norm.weight"]),
            "bias": cast(sd["final_layer_norm.bias"]),
        },
        "lm_head": {"kernel": castT(sd["embed_out.weight"])},
    }
    for i in range(config.num_hidden_layers):
        p = f"layers.{i}."
        params[f"h_{i}"] = {
            "ln_1": {
                "scale": cast(sd[p + "input_layernorm.weight"]),
                "bias": cast(sd[p + "input_layernorm.bias"]),
            },
            "ln_2": {
                "scale": cast(sd[p + "post_attention_layernorm.weight"]),
                "bias": cast(sd[p + "post_attention_layernorm.bias"]),
            },
            "attn": {
                "query_key_value": {
                    "kernel": castT(sd[p + "attention.query_key_value.weight"]),
                    "bias": cast(sd[p + "attention.query_key_value.bias"]),
                },
                "dense": {
                    "kernel": castT(sd[p + "attention.dense.weight"]),
                    "bias": cast(sd[p + "attention.dense.bias"]),
                },
            },
            "mlp": {
                "dense_h_to_4h": {
                    "kernel": castT(sd[p + "mlp.dense_h_to_4h.weight"]),
                    "bias": cast(sd[p + "mlp.dense_h_to_4h.bias"]),
                },
                "dense_4h_to_h": {
                    "kernel": castT(sd[p + "mlp.dense_4h_to_h.weight"]),
                    "bias": cast(sd[p + "mlp.dense_4h_to_h.bias"]),
                },
            },
        }
    return params


def load_neox_checkpoint(model_path: str, dtype: str = "float32"):
    from transformers import AutoModelForCausalLM

    model = AutoModelForCausalLM.from_pretrained(model_path, local_files_only=True)
    config = neox_config_from_hf(model.config)
    return config, convert_neox_state_dict(model.state_dict(), config, dtype)


def olmoe_config_from_hf(path_or_dict) -> "OlmoeConfig":
    from trlx_tpu.models.olmoe import OlmoeConfig

    if isinstance(path_or_dict, (str, os.PathLike)):
        with open(os.path.join(path_or_dict, "config.json")) as f:
            d = json.load(f)
    elif hasattr(path_or_dict, "to_dict"):
        d = path_or_dict.to_dict()
    else:
        d = dict(path_or_dict)
    # the published keys by their published names; what the family does
    # not build (grouped KV heads, clip_qkv, rope_scaling) it refuses
    return OlmoeConfig.from_dict(d)


def convert_olmoe_state_dict(
    state_dict: Mapping[str, Any], config, dtype: str = "float32"
) -> Dict[str, Any]:
    """HF ``OlmoeForCausalLM`` -> ``OlmoeModel`` params. Every matrix is a
    bias-free torch ``nn.Linear`` (kernels transpose); the per-expert
    ``mlp.experts.M.{gate,up,down}_proj`` stack on a leading ``[E]`` axis."""
    sd = {k.removeprefix("model."): v for k, v in state_dict.items()}
    cast = lambda t: jnp.asarray(_np(t), dtype=jnp.dtype(dtype))
    castT = lambda t: jnp.asarray(_np(t).T.copy(), dtype=jnp.dtype(dtype))

    params: Dict[str, Any] = {
        "wte": {"embedding": cast(sd["embed_tokens.weight"])},
        "ln_f": {"scale": cast(sd["norm.weight"])},
        "lm_head": {"kernel": castT(sd["lm_head.weight"])},
    }
    for i in range(config.num_hidden_layers):
        p = f"layers.{i}."
        a = p + "self_attn."
        experts = lambda name: jnp.stack([
            castT(sd[f"{p}mlp.experts.{m}.{name}.weight"]) for m in range(config.num_experts)
        ])
        params[f"h_{i}"] = {
            "ln_1": {"scale": cast(sd[p + "input_layernorm.weight"])},
            "ln_2": {"scale": cast(sd[p + "post_attention_layernorm.weight"])},
            "attn": {
                **{n: {"kernel": castT(sd[f"{a}{n}.weight"])}
                   for n in ("q_proj", "k_proj", "v_proj", "o_proj")},
                "q_norm": {"scale": cast(sd[a + "q_norm.weight"])},
                "k_norm": {"scale": cast(sd[a + "k_norm.weight"])},
            },
            "mlp": {
                "router": castT(sd[p + "mlp.gate.weight"]),
                "w_gate": experts("gate_proj"),
                "w_up": experts("up_proj"),
                "w_down": experts("down_proj"),
            },
        }
    return params


def load_olmoe_checkpoint(model_path: str, dtype: str = "float32"):
    from transformers import AutoModelForCausalLM

    model = AutoModelForCausalLM.from_pretrained(model_path, local_files_only=True)
    config = olmoe_config_from_hf(model.config)
    return config, convert_olmoe_state_dict(model.state_dict(), config, dtype)


def gpt_neo_config_from_hf(path_or_dict) -> "GPTNeoConfig":
    from trlx_tpu.models.gpt_neo import GPTNeoConfig, expand_attention_types

    if isinstance(path_or_dict, (str, os.PathLike)):
        with open(os.path.join(path_or_dict, "config.json")) as f:
            d = json.load(f)
    elif hasattr(path_or_dict, "to_dict"):
        d = path_or_dict.to_dict()
    else:
        d = dict(path_or_dict)
    return GPTNeoConfig(
        vocab_size=d["vocab_size"],
        max_position_embeddings=d.get("max_position_embeddings", 2048),
        hidden_size=d["hidden_size"],
        num_layers=d["num_layers"],
        num_heads=d["num_heads"],
        intermediate_size=d.get("intermediate_size"),
        window_size=d.get("window_size", 256),
        attention_layers=expand_attention_types(
            d.get("attention_types") or [], d["num_layers"]
        ),
        layer_norm_epsilon=d.get("layer_norm_epsilon", 1e-5),
    )


def convert_gpt_neo_state_dict(
    state_dict: Mapping[str, Any], config, dtype: str = "float32"
) -> Dict[str, Any]:
    """HF ``GPTNeoForCausalLM`` -> ``GPTNeoModel`` params.

    GPT-Neo uses torch ``nn.Linear`` everywhere (kernels transpose, unlike
    GPT-2's Conv1D); q/k/v are bias-free, ``out_proj`` and MLP carry biases;
    the LM head is tied to ``wte``.
    """
    sd = {k.removeprefix("transformer."): v for k, v in state_dict.items()}
    cast = lambda t: jnp.asarray(_np(t), dtype=jnp.dtype(dtype))
    castT = lambda t: jnp.asarray(_np(t).T.copy(), dtype=jnp.dtype(dtype))

    params: Dict[str, Any] = {
        "wte": {"embedding": cast(sd["wte.weight"])},
        "wpe": {"embedding": cast(sd["wpe.weight"])},
        "ln_f": {"scale": cast(sd["ln_f.weight"]), "bias": cast(sd["ln_f.bias"])},
    }
    for i in range(config.num_layers):
        p = f"h.{i}."
        a = p + "attn.attention."
        params[f"h_{i}"] = {
            "ln_1": {"scale": cast(sd[p + "ln_1.weight"]), "bias": cast(sd[p + "ln_1.bias"])},
            "ln_2": {"scale": cast(sd[p + "ln_2.weight"]), "bias": cast(sd[p + "ln_2.bias"])},
            "attn": {
                "q_proj": {"kernel": castT(sd[a + "q_proj.weight"])},
                "k_proj": {"kernel": castT(sd[a + "k_proj.weight"])},
                "v_proj": {"kernel": castT(sd[a + "v_proj.weight"])},
                "out_proj": {
                    "kernel": castT(sd[a + "out_proj.weight"]),
                    "bias": cast(sd[a + "out_proj.bias"]),
                },
            },
            "mlp": {
                "c_fc": {
                    "kernel": castT(sd[p + "mlp.c_fc.weight"]),
                    "bias": cast(sd[p + "mlp.c_fc.bias"]),
                },
                "c_proj": {
                    "kernel": castT(sd[p + "mlp.c_proj.weight"]),
                    "bias": cast(sd[p + "mlp.c_proj.bias"]),
                },
            },
        }
    return params


def load_gpt_neo_checkpoint(model_path: str, dtype: str = "float32"):
    from transformers import AutoModelForCausalLM

    model = AutoModelForCausalLM.from_pretrained(model_path, local_files_only=True)
    config = gpt_neo_config_from_hf(model.config)
    return config, convert_gpt_neo_state_dict(model.state_dict(), config, dtype)


def load_gpt2_checkpoint(model_path: str, dtype: str = "float32"):
    """Load an on-disk HF GPT-2 checkpoint -> (GPT2Config, param tree).

    Uses torch only to deserialize weights (host-side); never touches the
    network (offline-safe).
    """
    from transformers import AutoModelForCausalLM

    model = AutoModelForCausalLM.from_pretrained(model_path, local_files_only=True)
    config = gpt2_config_from_hf(model.config)
    params = convert_gpt2_state_dict(model.state_dict(), config, dtype)
    return config, params
