"""Work a step needs, computed from shapes: model FLOPs of a dense
decoder's training step and the HBM bytes of the int8 wire stage.

These are the yardsticks of ``mfu`` and ``int8_wire_roofline``; they depend
on the configuration and the traffic, never on how the program implements
the step.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

QUANT_BLOCK = 256        # elements per int8 scale
WIRE_ROW = 1024          # the wire pads each bucket to whole 1024-lane rows


def matmul_params(cfg: Dict) -> Dict[str, int]:
    """Parameters that enter a matrix multiplication, from a Hugging Face
    style decoder config: the layers' projections and the LM head (the
    tied embedding when ``tie_word_embeddings``).  Biases, norms and the
    embedding lookup do no matmul work and are left out."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    dh = cfg.get("head_dim") or d // h
    ff = cfg["intermediate_size"]
    layer = d * h * dh + 2 * d * kv * dh + h * dh * d + 3 * d * ff
    return {"layers": cfg["num_hidden_layers"] * layer,
            "lm_head": cfg["vocab_size"] * d}


def all_params(cfg: Dict) -> int:
    """Every parameter of the model (published count, unpadded vocab)."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    dh = cfg.get("head_dim") or d // h
    mm = matmul_params(cfg)
    bias = (h + 2 * kv) * dh          # q, k, v biases
    norms = 2 * d
    n = mm["layers"] + cfg["num_hidden_layers"] * (bias + norms) + d
    embed = cfg["vocab_size"] * d
    return n + (embed if cfg["tie_word_embeddings"] else 2 * embed)


def train_flops_per_token(cfg: Dict, seq_len: int) -> float:
    """Forward + backward FLOPs per token: 6 per matmul parameter, plus
    causal attention, 6 * S * H * d_head per layer (QK^T and PV at an
    average context of S/2, times three for the backward).  Recomputation
    under remat is not counted."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    dh = cfg.get("head_dim") or d // h
    mm = matmul_params(cfg)
    attn = 6.0 * seq_len * h * dh * cfg["num_hidden_layers"]
    return 6.0 * (mm["layers"] + mm["lm_head"]) + attn


def plan_buckets(leaf_sizes: Sequence[int], bucket_bytes: int,
                 elem_bytes: int = 4) -> List[int]:
    """Bucket sizes in elements: leaves packed greedily in tree order into
    buckets of at most ``bucket_bytes``; a larger leaf is a bucket of its
    own.  The order of issue does not change the bytes."""
    sizes: List[int] = []
    cur = 0
    for n in leaf_sizes:
        if cur and (cur + n) * elem_bytes > bucket_bytes:
            sizes.append(cur)
            cur = 0
        cur += n
    if cur:
        sizes.append(cur)
    return sizes


def int8_wire_bytes(bucket_sizes: Sequence[int], n_pods: int) -> Dict[str, float]:
    """HBM bytes of the int8 wire stage per step, per chip.

    quantize: read the f32 bucket, write int8 + one f32 scale per block.
    dequant_aggregate: read ``n_pods`` int8 payloads + their scales + one
    f32 weight each, write the f32 aggregate.
    Buckets are padded to whole wire rows before quantization.
    """
    quant = deq = 0.0
    for d in bucket_sizes:
        d_pad = -(-d // WIRE_ROW) * WIRE_ROW
        scales = 4.0 * d_pad / QUANT_BLOCK
        quant += 4.0 * d_pad + d_pad + scales
        deq += n_pods * (d_pad + scales) + 4.0 * n_pods + 4.0 * d
    return {"quantize": quant, "dequant_aggregate": deq,
            "total": quant + deq}
