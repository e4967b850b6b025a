"""Attention variants: GQA (blockwise/flash-style) and DeepSeek-V2 MLA.

Design notes (DESIGN.md §5):

* Training/prefill attention is *blockwise*: an online-softmax scan over KV
  blocks (the pure-jnp twin of the Pallas flash kernel in
  ``repro/kernels/flash_attention.py``) so 32k prefill never materializes
  an [S, S] score matrix.
* Decode attention is a plain einsum over the KV cache.  With the cache
  sequence dim sharded over the ``model`` mesh axis, GSPMD lowers the
  softmax reductions into exactly the flash-decoding partial-max/sum
  combine (small all-reduces) — the TPU analogue of MLfabric's in-network
  partial aggregation.
* MLA keeps the latent ``c_kv`` cache (kv_lora + rope dims) and decodes in
  the *absorbed* form, so the 32k cache stays compressed.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import MLAConfig, ModelConfig
from .layers import Params, apply_rope, dense_init

NEG_INF = -1e30

# attention implementation: "blockwise" (pure-jnp online softmax; the
# GSPMD/dry-run path) or "pallas" (the flash kernel in repro/kernels, run
# as ``kernels.ops.interpret_mode`` decides).
_ATTN_IMPL = "blockwise"


def set_attention_impl(impl: str) -> None:
    global _ATTN_IMPL
    assert impl in ("blockwise", "pallas"), impl
    _ATTN_IMPL = impl


def get_attention_impl() -> str:
    return _ATTN_IMPL


# --------------------------------------------------------------------------- #
# core blockwise attention (shared by GQA and MLA prefill)
# --------------------------------------------------------------------------- #
def _plain_attention(q, k, v, mask_bias, scale):
    """q: [B,Sq,H,D] k,v: [B,Skv,KVH,Dk/Dv] -> [B,Sq,H,Dv] (f32 softmax)."""
    b, sq, h, dk = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, sq, kvh, g, dk)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32) * scale
    scores = scores + mask_bias  # [1,1,1,Sq,Skv] broadcast
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v)
    return out.reshape(b, sq, h, v.shape[-1])


def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool, q_offset: int = 0,
                        kv_block: int = 512,
                        scale: Optional[float] = None) -> jax.Array:
    """Online-softmax attention, scanning over KV blocks.

    q: [B, Sq, H, Dk]; k: [B, Skv, KVH, Dk]; v: [B, Skv, KVH, Dv].
    GQA is handled by grouping H into KVH groups.  ``q_offset`` gives the
    absolute position of q[0] for causal masking (sequence-sharded callers).

    Differentiation goes through a flash-style custom VJP: forward saves
    only (q, k, v, out, lse); backward recomputes each block's scores —
    O(block) transient memory instead of O(n_blocks) stacked carries.
    """
    b, sq, h, dk = q.shape
    _, skv, kvh, dv = v.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(dk)

    if (_ATTN_IMPL == "pallas" and dk == dv and q_offset == 0 and sq == skv
            and sq % 16 == 0):
        from ..kernels.flash_attention import flash_attention
        from ..kernels.ops import interpret_mode
        out = flash_attention(q.transpose(0, 2, 1, 3),
                              k.transpose(0, 2, 1, 3),
                              v.transpose(0, 2, 1, 3), causal=causal,
                              scale=scale,
                              block_q=min(128, sq), block_k=min(128, skv),
                              interpret=interpret_mode())
        return out.transpose(0, 2, 1, 3)

    if skv <= kv_block:  # small sequences: one block, no scan
        mask = _causal_bias(sq, skv, q_offset, 0, causal)
        return _plain_attention(q, k, v, mask, scale)

    if skv % kv_block != 0:  # e.g. whisper's 1500 frames: use a divisor
        kv_block = next(b for b in range(kv_block, 0, -1) if skv % b == 0)
    return _flash_vjp(q, k, v, causal, q_offset, kv_block, scale)


def _block_mask(sq, kv_block, q_offset, blk):
    q_pos = q_offset + jnp.arange(sq)
    k_pos = blk * kv_block + jnp.arange(kv_block)
    return q_pos[:, None] >= k_pos[None, :]


def _flash_fwd_core(q, k, v, causal, q_offset, kv_block, scale):
    """Returns (out [B,Sq,H,Dv], lse [B,KVH,G,Sq] f32)."""
    b, sq, h, dk = q.shape
    _, skv, kvh, dv = v.shape
    n_blocks = skv // kv_block
    g = h // kvh
    qg = q.reshape(b, sq, kvh, g, dk)
    kb = k.reshape(b, n_blocks, kv_block, kvh, dk).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, n_blocks, kv_block, kvh, dv).transpose(1, 0, 2, 3, 4)

    def body(carry, xs):
        m_prev, l_prev, o_prev, blk = carry
        kk, vv = xs
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kk,
                            preferred_element_type=jnp.float32) * scale
        if causal:
            scores = jnp.where(_block_mask(sq, kv_block, q_offset, blk),
                               scores, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new[..., None])
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        o_new = (o_prev * alpha[..., None]
                 + jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(vv.dtype), vv
                              ).astype(jnp.float32))
        return (m_new, l_new, o_new, blk + 1), None

    m0 = jnp.full((b, kvh, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kvh, g, sq), jnp.float32)
    o0 = jnp.zeros((b, kvh, g, sq, dv), jnp.float32)
    (m, l, o, _), _ = jax.lax.scan(body, (m0, l0, o0, jnp.zeros((), jnp.int32)),
                                   (kb, vb))
    l_safe = jnp.maximum(l, 1e-30)
    out = (o / l_safe[..., None]).transpose(0, 3, 1, 2, 4)
    lse = m + jnp.log(l_safe)
    return out.reshape(b, sq, h, dv).astype(q.dtype), lse


import functools as _functools


@_functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_vjp(q, k, v, causal, q_offset, kv_block, scale):
    out, _ = _flash_fwd_core(q, k, v, causal, q_offset, kv_block, scale)
    return out


def _flash_vjp_fwd(q, k, v, causal, q_offset, kv_block, scale):
    out, lse = _flash_fwd_core(q, k, v, causal, q_offset, kv_block, scale)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(causal, q_offset, kv_block, scale, res, do):
    q, k, v, out, lse = res
    b, sq, h, dk = q.shape
    _, skv, kvh, dv = v.shape
    n_blocks = skv // kv_block
    g = h // kvh

    qg = q.reshape(b, sq, kvh, g, dk).astype(jnp.float32)
    dog = do.reshape(b, sq, kvh, g, dv).astype(jnp.float32)
    outg = out.reshape(b, sq, kvh, g, dv).astype(jnp.float32)
    # delta = rowsum(do * out): the softmax-jacobian diagonal correction
    delta = jnp.einsum("bqhgd,bqhgd->bhgq", dog, outg)

    kb = k.reshape(b, n_blocks, kv_block, kvh, dk).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, n_blocks, kv_block, kvh, dv).transpose(1, 0, 2, 3, 4)

    def body(dq_acc, xs):
        kk, vv, blk = xs
        kkf = kk.astype(jnp.float32)
        vvf = vv.astype(jnp.float32)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kkf) * scale
        p = jnp.exp(s - lse[..., None])                     # [b,kvh,g,sq,bk]
        if causal:
            p = jnp.where(_block_mask(sq, kv_block, q_offset, blk), p, 0.0)
        dv_blk = jnp.einsum("bhgqk,bqhgd->bkhd", p, dog)
        dp = jnp.einsum("bqhgd,bkhd->bhgqk", dog, vvf)
        ds = p * (dp - delta[..., None]) * scale
        dq_acc = dq_acc + jnp.einsum("bhgqk,bkhd->bqhgd", ds, kkf)
        dk_blk = jnp.einsum("bhgqk,bqhgd->bkhd", ds, qg)
        return dq_acc, (dk_blk, dv_blk)

    dq0 = jnp.zeros((b, sq, kvh, g, dk), jnp.float32)
    dq, (dk_blocks, dv_blocks) = jax.lax.scan(
        body, dq0, (kb, vb, jnp.arange(n_blocks)))
    dk_out = dk_blocks.transpose(1, 0, 2, 3, 4).reshape(b, skv, kvh, dk)
    dv_out = dv_blocks.transpose(1, 0, 2, 3, 4).reshape(b, skv, kvh, dv)
    return (dq.reshape(b, sq, h, dk).astype(q.dtype),
            dk_out.astype(k.dtype), dv_out.astype(v.dtype))


_flash_vjp.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _causal_bias(sq, skv, q_offset, k_offset, causal):
    if not causal:
        return jnp.zeros((1, 1, 1, sq, skv), jnp.float32)
    q_pos = q_offset + jnp.arange(sq)
    k_pos = k_offset + jnp.arange(skv)
    return jnp.where(q_pos[:, None] >= k_pos[None, :], 0.0,
                     NEG_INF)[None, None, None]


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     length: jax.Array, *,
                     scale: Optional[float] = None) -> jax.Array:
    """One-token attention over a [B, S, KVH, D] cache.

    ``length``: number of valid cache positions (scalar).  Invalid slots are
    masked.  The softmax reductions over S lower to the flash-decoding
    combine when S is sharded.
    """
    b, s, kvh, dk = k_cache.shape
    h = q.shape[1]              # q: [B, H, D]
    g = h // kvh
    dv = v_cache.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(dk)
    qg = q.reshape(b, kvh, g, dk)
    scores = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache,
                        preferred_element_type=jnp.float32) * scale
    valid = (jnp.arange(s) < length)[None, None, None, :]
    scores = jnp.where(valid, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", probs.astype(v_cache.dtype), v_cache)
    return out.reshape(b, 1, h, dv)


# --------------------------------------------------------------------------- #
# GQA attention layer
# --------------------------------------------------------------------------- #
def init_gqa(key: jax.Array, cfg: ModelConfig, dtype=jnp.bfloat16) -> Params:
    d, h, kvh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p: Params = {
        "wq": dense_init(ks[0], d, h * hd, dtype=dtype),
        "wk": dense_init(ks[1], d, kvh * hd, dtype=dtype),
        "wv": dense_init(ks[2], d, kvh * hd, dtype=dtype),
        "wo": dense_init(ks[3], h * hd, d, scale=1.0 / math.sqrt(2 * cfg.n_layers),
                         dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dtype=dtype)
        p["bk"] = jnp.zeros((kvh * hd,), dtype=dtype)
        p["bv"] = jnp.zeros((kvh * hd,), dtype=dtype)
    return p


def _qkv(p: Params, x: jax.Array, cfg: ModelConfig):
    b, s, _ = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    return (q.reshape(b, s, h, hd), k.reshape(b, s, kvh, hd),
            v.reshape(b, s, kvh, hd))


def gqa_forward(p: Params, x: jax.Array, cfg: ModelConfig, *,
                positions: Optional[jax.Array] = None, causal: bool = True,
                kv_block: int = 512,
                xattn_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Full-sequence attention (train / prefill).  Returns (out, kv) where kv
    is the cache contribution {k, v}: [B, S, KVH, D]."""
    b, s, _ = x.shape
    q, k, v = _qkv(p, x, cfg)
    if xattn_kv is not None:
        k, v = xattn_kv  # cross-attention: encoder keys/values
        causal = False
    elif cfg.rope:
        pos = positions if positions is not None else jnp.arange(s)
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    out = blockwise_attention(q, k, v, causal=causal, kv_block=kv_block)
    return out.reshape(b, s, -1) @ p["wo"], {"k": k, "v": v}


def gqa_decode(p: Params, x: jax.Array, cache: Dict[str, jax.Array],
               pos: jax.Array, cfg: ModelConfig,
               ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode. x: [B, 1, d]; cache {k, v}: [B, S, KVH, D];
    ``pos``: current position scalar (cache length so far)."""
    b = x.shape[0]
    q, k, v = _qkv(p, x, cfg)
    if cfg.rope:
        posv = jnp.full((1,), pos)
        q = apply_rope(q, posv, cfg.rope_theta)
        k = apply_rope(k, posv, cfg.rope_theta)
    k_cache = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                           (0, pos, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                           (0, pos, 0, 0))
    out = decode_attention(q[:, 0], k_cache, v_cache, pos + 1)
    return out.reshape(b, 1, -1) @ p["wo"], {"k": k_cache, "v": v_cache}


def gqa_decode_q8(p: Params, x: jax.Array, cache: Dict[str, jax.Array],
                  pos: jax.Array, cfg,
                  ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode against an **int8-quantized** KV cache.

    Cache: {k_q, v_q: int8 [B,S,KVH,D]; k_s, v_s: f32 [B,S,KVH]} — per
    (position, kv-head) symmetric scales, exactly the block layout of the
    Pallas quantize kernel.  Halves decode HBM traffic vs bf16 (the decode
    roofline's dominant term) at ~0.4% max logit error (tests).
    """
    b = x.shape[0]
    q, k, v = _qkv(p, x, cfg)
    if cfg.rope:
        posv = jnp.full((1,), pos)
        q = apply_rope(q, posv, cfg.rope_theta)
        k = apply_rope(k, posv, cfg.rope_theta)

    def quant(t):  # [B,1,KVH,D] -> int8 + per-(B,1,KVH) scale
        tf32 = t.astype(jnp.float32)
        s = jnp.maximum(jnp.max(jnp.abs(tf32), axis=-1) / 127.0, 1e-30)
        qv = jnp.clip(jnp.round(tf32 / s[..., None]), -127, 127
                      ).astype(jnp.int8)
        return qv, s

    k_qn, k_sn = quant(k)
    v_qn, v_sn = quant(v)
    k_q = jax.lax.dynamic_update_slice(cache["k_q"], k_qn, (0, pos, 0, 0))
    v_q = jax.lax.dynamic_update_slice(cache["v_q"], v_qn, (0, pos, 0, 0))
    k_s = jax.lax.dynamic_update_slice(cache["k_s"], k_sn, (0, pos, 0))
    v_s = jax.lax.dynamic_update_slice(cache["v_s"], v_sn, (0, pos, 0))

    kvh = k_q.shape[2]
    h = q.shape[2]
    g = h // kvh
    dk = q.shape[-1]
    scale = 1.0 / math.sqrt(dk)
    qg = q[:, 0].reshape(b, kvh, g, dk)
    # scores on the int8 payload, per-position scales folded in afterwards
    scores = jnp.einsum("bhgd,bkhd->bhgk", qg.astype(jnp.float32),
                        k_q.astype(jnp.float32)) * scale
    scores = scores * k_s.transpose(0, 2, 1)[:, :, None, :]
    valid = (jnp.arange(k_q.shape[1]) < pos + 1)[None, None, None, :]
    probs = jax.nn.softmax(jnp.where(valid, scores, NEG_INF), axis=-1)
    probs_v = probs * v_s.transpose(0, 2, 1)[:, :, None, :]
    out = jnp.einsum("bhgk,bkhd->bhgd", probs_v,
                     v_q.astype(jnp.float32))
    out = out.reshape(b, 1, h * v_q.shape[-1]).astype(x.dtype) @ p["wo"]
    return out, {"k_q": k_q, "v_q": v_q, "k_s": k_s, "v_s": v_s}


def gqa_cross_decode(p: Params, x: jax.Array, k: jax.Array, v: jax.Array,
                     n_valid: jax.Array) -> jax.Array:
    """Cross-attention for one decode token against fixed encoder KV."""
    b = x.shape[0]
    hd = k.shape[3]
    q = x @ p["wq"]
    if "bq" in p:
        q = q + p["bq"]
    q = q.reshape(b, q.shape[-1] // hd, hd)  # [B, H, D]
    out = decode_attention(q, k, v, n_valid)
    return out.reshape(b, 1, -1) @ p["wo"]


# --------------------------------------------------------------------------- #
# DeepSeek-V2 MLA
# --------------------------------------------------------------------------- #
def init_mla(key: jax.Array, cfg: ModelConfig, dtype=jnp.bfloat16) -> Params:
    m: MLAConfig = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 6)
    return {
        "q_down": dense_init(ks[0], d, m.q_lora_rank, dtype=dtype),
        "q_up": dense_init(ks[1], m.q_lora_rank, h * qk, dtype=dtype),
        "kv_down": dense_init(ks[2], d, m.kv_lora_rank + m.qk_rope_head_dim,
                              dtype=dtype),
        "k_up": dense_init(ks[3], m.kv_lora_rank, h * m.qk_nope_head_dim,
                           dtype=dtype),
        "v_up": dense_init(ks[4], m.kv_lora_rank, h * m.v_head_dim, dtype=dtype),
        "wo": dense_init(ks[5], h * m.v_head_dim, d,
                         scale=1.0 / math.sqrt(2 * cfg.n_layers), dtype=dtype),
    }


def mla_forward(p: Params, x: jax.Array, cfg: ModelConfig, *,
                positions: Optional[jax.Array] = None,
                kv_block: int = 512) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """MLA train/prefill.  Cache contribution: latent {ckv, krope}."""
    m: MLAConfig = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    pos = positions if positions is not None else jnp.arange(s)

    q = (x @ p["q_down"]) @ p["q_up"]
    q = q.reshape(b, s, h, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, pos, cfg.rope_theta)

    kv = x @ p["kv_down"]                                  # [B,S,R+rope]
    ckv, krope = jnp.split(kv, [m.kv_lora_rank], axis=-1)
    krope = apply_rope(krope[:, :, None, :], pos, cfg.rope_theta)  # [B,S,1,rope]

    k_nope = (ckv @ p["k_up"]).reshape(b, s, h, m.qk_nope_head_dim)
    v = (ckv @ p["v_up"]).reshape(b, s, h, m.v_head_dim)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        krope, (b, s, h, m.qk_rope_head_dim)).astype(k_nope.dtype)], axis=-1)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)

    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    out = blockwise_attention(q_full, k, v, causal=True, kv_block=kv_block,
                              scale=scale)
    out = out.reshape(b, s, -1) @ p["wo"]
    return out, {"ckv": ckv, "krope": krope[:, :, 0, :]}


def mla_decode(p: Params, x: jax.Array, cache: Dict[str, jax.Array],
               pos: jax.Array, cfg: ModelConfig,
               ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Absorbed-form MLA decode against the latent cache.

    cache: {ckv: [B, S, R], krope: [B, S, rope]}.  Scores are computed in
    the latent space (q absorbed through k_up), the attention output in
    latent space is expanded through v_up — the cache stays compressed.
    """
    m: MLAConfig = cfg.mla
    b = x.shape[0]
    h = cfg.n_heads
    r = m.kv_lora_rank

    q = (x @ p["q_down"]) @ p["q_up"]
    q = q.reshape(b, 1, h, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    posv = jnp.full((1,), pos)
    q_rope = apply_rope(q_rope, posv, cfg.rope_theta)[:, 0]   # [B,H,rope]

    kv = x[:, 0] @ p["kv_down"]                               # [B,R+rope]
    ckv_new, krope_new = jnp.split(kv, [r], axis=-1)
    krope_new = apply_rope(krope_new[:, None, None, :], posv,
                           cfg.rope_theta)[:, 0, 0]
    ckv = jax.lax.dynamic_update_slice(cache["ckv"],
                                       ckv_new[:, None].astype(cache["ckv"].dtype),
                                       (0, pos, 0))
    krope = jax.lax.dynamic_update_slice(
        cache["krope"], krope_new[:, None].astype(cache["krope"].dtype),
        (0, pos, 0))

    # absorb: q_eff[b,h,r] = q_nope . k_up^T  (k_up: [R, H*nope])
    k_up = p["k_up"].reshape(r, h, m.qk_nope_head_dim)
    q_eff = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], k_up)
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    scores = (jnp.einsum("bhr,bkr->bhk", q_eff, ckv,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bhr,bkr->bhk", q_rope, krope,
                           preferred_element_type=jnp.float32)) * scale
    valid = (jnp.arange(ckv.shape[1]) < pos + 1)[None, None, :]
    probs = jax.nn.softmax(jnp.where(valid, scores, NEG_INF), axis=-1)
    out_latent = jnp.einsum("bhk,bkr->bhr", probs.astype(ckv.dtype), ckv)
    v_up = p["v_up"].reshape(r, h, m.v_head_dim)
    out = jnp.einsum("bhr,rhd->bhd", out_latent, v_up)
    out = out.reshape(b, 1, h * m.v_head_dim) @ p["wo"]
    return out, {"ckv": ckv, "krope": krope}
