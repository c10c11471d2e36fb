"""The Llama-style decoder block: RMSNorm, grouped-query attention with
RoPE, SwiGLU MLP, an untied LM head (SmolLM).

Initialisation follows the published Llama recipe: every matrix and the
embedding ``normal(0, initializer_range)``, every RMSNorm scale 1.
"""
from __future__ import annotations


def _L(a):
    return a["num_hidden_layers"]


def _H(a):
    return a["num_attention_heads"]


def _KV(a):
    return a["num_key_value_heads"]


def _hd(a):
    return a.get("head_dim") or a["hidden_size"] // a["num_attention_heads"]


# canonical name -> (shape builder, init, stacked (L, ...) per layer)
LAYOUT = {
    "embed": (lambda a: (a["vocab_size"], a["hidden_size"]), "normal", False),
    "final_norm": (lambda a: (a["hidden_size"],), "ones", False),
    "lm_head": (lambda a: (a["hidden_size"], a["vocab_size"]), "normal", False),
    "layers/ln1": (lambda a: (_L(a), a["hidden_size"]), "ones", True),
    "layers/ln2": (lambda a: (_L(a), a["hidden_size"]), "ones", True),
    "layers/wq": (lambda a: (_L(a), a["hidden_size"], _H(a), _hd(a)), "normal", True),
    "layers/wk": (lambda a: (_L(a), a["hidden_size"], _KV(a), _hd(a)), "normal", True),
    "layers/wv": (lambda a: (_L(a), a["hidden_size"], _KV(a), _hd(a)), "normal", True),
    "layers/wo": (lambda a: (_L(a), _H(a), _hd(a), a["hidden_size"]), "normal", True),
    "layers/w1": (lambda a: (_L(a), a["hidden_size"], a["intermediate_size"]), "normal", True),
    "layers/w3": (lambda a: (_L(a), a["hidden_size"], a["intermediate_size"]), "normal", True),
    "layers/w2": (lambda a: (_L(a), a["intermediate_size"], a["hidden_size"]), "normal", True),
}

# canonical name -> path in the program's params
PATHS = {
    "embed": ("embed",), "final_norm": ("final_norm",), "lm_head": ("lm_head",),
    "layers/ln1": ("blocks", "ln1"), "layers/ln2": ("blocks", "ln2"),
    "layers/wq": ("blocks", "attn", "wq"), "layers/wk": ("blocks", "attn", "wk"),
    "layers/wv": ("blocks", "attn", "wv"), "layers/wo": ("blocks", "attn", "wo"),
    "layers/w1": ("blocks", "mlp", "w1"), "layers/w2": ("blocks", "mlp", "w2"),
    "layers/w3": ("blocks", "mlp", "w3"),
}

TINY = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
            num_key_value_heads=2, num_hidden_layers=2, vocab_size=256)


def model_config(arch: dict):
    """The program's ModelConfig of a Llama-style configuration file."""
    from repro.configs.base import ModelConfig
    if arch.get("tie_word_embeddings"):
        raise ValueError("the program has no tied LM head")
    return ModelConfig(
        name=arch["name"], family="dense",
        n_layers=arch["num_hidden_layers"], d_model=arch["hidden_size"],
        n_heads=arch["num_attention_heads"],
        n_kv_heads=arch["num_key_value_heads"],
        d_ff=arch["intermediate_size"], vocab=arch["vocab_size"],
        head_dim=arch.get("head_dim", 0), rope_theta=arch["rope_theta"],
        norm_eps=arch["rms_norm_eps"], dtype=arch["torch_dtype"])


def matmul_params(arch: dict) -> int:
    """Parameters that enter a matrix multiplication once per token: every
    attention projection, the MLP and the LM head (not the embedding
    gather, not the norms)."""
    d, f, l = arch["hidden_size"], arch["intermediate_size"], arch["num_hidden_layers"]
    hd = _hd(arch)
    attn = 2 * d * arch["num_attention_heads"] * hd + 2 * d * arch["num_key_value_heads"] * hd
    mlp = 3 * d * f
    return l * (attn + mlp) + d * arch["vocab_size"]


def model_flops_per_token(arch: dict, seq: int) -> float:
    """Forward and backward FLOPs one trained token requires: 6 per matmul
    parameter, plus causal attention, 4 * (seq / 2) * heads * head_dim per
    layer for the forward (QK^T and PV over the average causal span), three
    times over for forward and backward."""
    attn = 4 * (seq / 2) * arch["num_attention_heads"] * _hd(arch) * arch["num_hidden_layers"]
    return 6.0 * matmul_params(arch) + 3.0 * attn
