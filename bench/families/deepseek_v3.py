"""The DeepSeek-V3 decoder block (Moonlight): multi-head latent attention,
one leading dense layer, then expert layers with a sigmoid-scored router
over all routed experts, the chip's held range of them, and shared experts;
RMSNorm, RoPE, an untied LM head.

The leading dense layer's leaves (``dense/*``) are unstacked; the expert
layers' (``layers/*``) are stacked along their first axis.  A configuration
states how many routed experts this chip holds (``n_routed_experts``,
listed in its ``reduced``), from which one (``expert_offset``), and the
router's width, the published count (``n_routed_experts_published``).

Initialisation follows the published DeepSeek recipe: every matrix and the
embedding ``normal(0, initializer_range)``, every RMSNorm scale 1.
"""
from __future__ import annotations


def _D(a):
    return a["hidden_size"]


def _H(a):
    return a["num_attention_heads"]


def _R(a):
    return a["kv_lora_rank"]


def _qk(a):
    return a["qk_nope_head_dim"] + a["qk_rope_head_dim"]


def _kv_b(a):
    return a["qk_nope_head_dim"] + a["v_head_dim"]


def _Lm(a):
    """The expert layers: every layer after the leading dense one."""
    return a["num_hidden_layers"] - a["first_k_dense_replace"]


def _E(a):
    return a["n_routed_experts"]


def _F(a):
    return a["moe_intermediate_size"]


def _Fs(a):
    return a["n_shared_experts"] * a["moe_intermediate_size"]


def _attention(prefix, L):
    """MLA's leaves, with a leading layer axis of ``L(a)`` when stacked."""
    def shape(build):
        return (lambda a: (L(a),) + build(a)) if L else build
    stacked = L is not None
    return {
        f"{prefix}/ln1": (shape(lambda a: (_D(a),)), "ones", stacked),
        f"{prefix}/ln2": (shape(lambda a: (_D(a),)), "ones", stacked),
        f"{prefix}/wq": (shape(lambda a: (_D(a), _H(a), _qk(a))), "normal", stacked),
        f"{prefix}/wkv_a": (shape(lambda a: (_D(a), _R(a) + a["qk_rope_head_dim"])),
                            "normal", stacked),
        f"{prefix}/kv_norm": (shape(lambda a: (_R(a),)), "ones", stacked),
        f"{prefix}/wkv_b": (shape(lambda a: (_R(a), _H(a), _kv_b(a))), "normal", stacked),
        f"{prefix}/wo": (shape(lambda a: (_H(a), a["v_head_dim"], _D(a))), "normal", stacked),
    }


# canonical name -> (shape builder, init, stacked (L, ...) per layer)
LAYOUT = {
    "embed": (lambda a: (a["vocab_size"], _D(a)), "normal", False),
    "final_norm": (lambda a: (_D(a),), "ones", False),
    "lm_head": (lambda a: (_D(a), a["vocab_size"]), "normal", False),
    **_attention("dense", None),
    "dense/w1": (lambda a: (_D(a), a["intermediate_size"]), "normal", False),
    "dense/w3": (lambda a: (_D(a), a["intermediate_size"]), "normal", False),
    "dense/w2": (lambda a: (a["intermediate_size"], _D(a)), "normal", False),
    **_attention("layers", _Lm),
    "layers/router": (lambda a: (_Lm(a), _D(a), a["n_routed_experts_published"]),
                      "normal", True),
    "layers/w1": (lambda a: (_Lm(a), _E(a), _D(a), _F(a)), "normal", True),
    "layers/w3": (lambda a: (_Lm(a), _E(a), _D(a), _F(a)), "normal", True),
    "layers/w2": (lambda a: (_Lm(a), _E(a), _F(a), _D(a)), "normal", True),
    "layers/shared_w1": (lambda a: (_Lm(a), _D(a), _Fs(a)), "normal", True),
    "layers/shared_w3": (lambda a: (_Lm(a), _D(a), _Fs(a)), "normal", True),
    "layers/shared_w2": (lambda a: (_Lm(a), _Fs(a), _D(a)), "normal", True),
}


def _attention_paths(prefix, at):
    return {f"{prefix}/ln1": at + ("ln1",), f"{prefix}/ln2": at + ("ln2",),
            **{f"{prefix}/{k}": at + ("attn", k)
               for k in ("wq", "wkv_a", "kv_norm", "wkv_b", "wo")}}


# canonical name -> path in the program's params
PATHS = {
    "embed": ("embed",), "final_norm": ("final_norm",), "lm_head": ("lm_head",),
    **_attention_paths("dense", ("dense", "0")),
    **{f"dense/{k}": ("dense", "0", "mlp", k) for k in ("w1", "w2", "w3")},
    **_attention_paths("layers", ("blocks",)),
    **{f"layers/{k}": ("blocks", "moe", k) for k in ("router", "w1", "w2", "w3")},
    **{f"layers/shared_{k}": ("blocks", "moe", "shared", k) for k in ("w1", "w2", "w3")},
}

TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
            kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, intermediate_size=128, moe_intermediate_size=32,
            n_routed_experts=4, n_routed_experts_published=8,
            num_experts_per_tok=3, num_hidden_layers=3, vocab_size=256)


def model_config(arch: dict):
    """The program's ModelConfig of a DeepSeek-V3 configuration file."""
    from repro.configs.base import ModelConfig
    unsupported = {
        "q_lora_rank": arch["q_lora_rank"] is not None,
        "tie_word_embeddings": arch["tie_word_embeddings"],
        "first_k_dense_replace": arch["first_k_dense_replace"] != 1,
        "moe_layer_freq": arch["moe_layer_freq"] != 1,
        "scoring_func": arch["scoring_func"] != "sigmoid",
        "topk_method": arch["topk_method"] != "noaux_tc",
        "n_group": arch["n_group"] != 1 or arch["topk_group"] != 1,
    }
    if any(unsupported.values()):
        raise ValueError(f"the program has no such block: "
                         f"{[k for k, v in unsupported.items() if v]}")
    return ModelConfig(
        name=arch["name"], family="moe",
        n_layers=arch["num_hidden_layers"], d_model=_D(arch),
        n_heads=_H(arch), n_kv_heads=arch["num_key_value_heads"],
        d_ff=0, vocab=arch["vocab_size"], rope_theta=arch["rope_theta"],
        norm_eps=arch["rms_norm_eps"], dtype=arch["torch_dtype"],
        n_experts=arch["n_routed_experts_published"], top_k=arch["num_experts_per_tok"],
        d_ff_expert=_F(arch), router="sigmoid",
        routed_scaling=arch["routed_scaling_factor"], norm_topk=arch["norm_topk_prob"],
        seq_aux_alpha=arch["seq_aux_alpha"] if arch["seq_aux"] else 0.0,
        n_shared_experts=arch["n_shared_experts"],
        expert_offset=arch["expert_offset"], n_experts_held=_E(arch),
        first_k_dense=arch["first_k_dense_replace"], d_ff_dense=arch["intermediate_size"],
        kv_lora_rank=_R(arch), qk_nope_head_dim=arch["qk_nope_head_dim"],
        qk_rope_head_dim=arch["qk_rope_head_dim"], v_head_dim=arch["v_head_dim"])


def matmul_params(arch: dict) -> float:
    """Parameters that enter a matrix multiplication once per token: every
    layer's attention projections, the dense layer's MLP, each expert
    layer's router and shared experts, the held routed experts at the share
    of a token's ``top_k`` choices that fall on them (``top_k * held /
    router width`` of one expert's three matrices), and the LM head (not
    the embedding gather, not the norms)."""
    d, h, r = _D(arch), _H(arch), _R(arch)
    attn = (d * h * _qk(arch) + d * (r + arch["qk_rope_head_dim"])
            + r * h * _kv_b(arch) + h * arch["v_head_dim"] * d)
    dense_mlp = 3 * d * arch["intermediate_size"]
    router = d * arch["n_routed_experts_published"]
    shared = 3 * d * _Fs(arch)
    routed = (arch["num_experts_per_tok"] * _E(arch) / arch["n_routed_experts_published"]
              * 3 * d * _F(arch))
    return (arch["num_hidden_layers"] * attn + dense_mlp
            + _Lm(arch) * (router + shared + routed) + d * arch["vocab_size"])


def model_flops_per_token(arch: dict, seq: int) -> float:
    """Forward and backward FLOPs one trained token requires: 6 per matmul
    parameter, plus causal attention, 2 * (seq / 2) * heads * (q/k width +
    v width) per layer for the forward (QK^T and PV over the average causal
    span), three times over for forward and backward."""
    attn = (2 * (seq / 2) * _H(arch) * (_qk(arch) + arch["v_head_dim"])
            * arch["num_hidden_layers"])
    return 6.0 * matmul_params(arch) + 3.0 * attn
