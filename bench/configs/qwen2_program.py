"""How the program under test holds a Qwen2 configuration and its weights.

``program_config`` expresses the Hugging Face style file as the program's
``ModelConfig``; ``to_program`` and ``from_program`` move weights between
the reference's names and the program's parameter tree.  The program calls
the silu branch of its gated MLP ``up`` and the linear branch ``gate``, so
``gate_proj`` is its ``up`` and ``up_proj`` its ``gate``.  It pads the
vocabulary rows of the embedding to a multiple of 256; the padded rows are
zero and take no part in the loss.
"""

from __future__ import annotations

from typing import Dict

import jax.numpy as jnp

from repro.configs.base import ModelConfig

# reference name -> path in the program's parameter tree
PATHS = {
    "embed": ("embeds", "embed"),
    "final_norm": ("final_norm", "scale"),
    "input_layernorm": ("layers", "norm1", "scale"),
    "post_attention_layernorm": ("layers", "norm2", "scale"),
    "q_proj": ("layers", "mix", "wq"), "q_bias": ("layers", "mix", "bq"),
    "k_proj": ("layers", "mix", "wk"), "k_bias": ("layers", "mix", "bk"),
    "v_proj": ("layers", "mix", "wv"), "v_bias": ("layers", "mix", "bv"),
    "o_proj": ("layers", "mix", "wo"),
    "gate_proj": ("layers", "mlp", "up"),
    "up_proj": ("layers", "mlp", "gate"),
    "down_proj": ("layers", "mlp", "down"),
}


def program_config(cfg: Dict) -> ModelConfig:
    if not cfg["tie_word_embeddings"]:
        raise ValueError("untied Qwen2 heads are not wired to the program")
    return ModelConfig(
        name=cfg.get("name", "qwen2"), family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_head=cfg.get("head_dim", 0) or 0,
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        layer_pattern="a", qkv_bias=True, rope=True,
        rope_theta=cfg["rope_theta"], norm="rmsnorm", act="silu",
        tie_embeddings=True, source=cfg["source"])


def to_program(weights: Dict, abstract) -> Dict:
    """Reference-named weights -> the program's tree, shaped as
    ``abstract`` (the program's parameter shapes)."""
    tree: Dict = {}
    for name, path in PATHS.items():
        node = abstract
        for p in path:
            node = node[p]
        w = weights[name]
        if w.shape != node.shape:            # padded vocabulary rows
            w = jnp.pad(w, [(0, n - m) for m, n in zip(w.shape, node.shape)])
        sub = tree
        for p in path[:-1]:
            sub = sub.setdefault(p, {})
        sub[path[-1]] = w.astype(node.dtype)
    return tree


def from_program(tree: Dict) -> Dict:
    out = {}
    for name, path in PATHS.items():
        node = tree
        for p in path:
            node = node[p]
        out[name] = node
    return out
