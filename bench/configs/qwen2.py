"""Plain reference of a Qwen2 decoder (arXiv:2407.10671; Hugging Face
``Qwen2ForCausalLM``) for training: forward, next-token loss, gradients and
the momentum-SGD update, in ``jax.numpy`` and float32.

It follows the published description and imports nothing of the program:

* token embedding (tied with the LM head when ``tie_word_embeddings``);
* per layer: RMSNorm -> q/k/v projections with bias -> rotary embedding
  (rotate-half form, base ``rope_theta``) -> causal grouped-query
  attention (each K/V head serves ``heads / kv_heads`` query heads) ->
  output projection without bias -> residual; RMSNorm ->
  ``down(silu(gate(x)) * up(x))`` -> residual;
* final RMSNorm, logits over the vocabulary, mean cross-entropy over every
  token of the batch.

Weights are stored as ``x @ W`` matrices ``[d_in, d_out]``, stacked over
layers, under Hugging Face's names.  ``init_weights`` makes them from a key
in the configuration's ``torch_dtype``; the harness gives the program the
same weights, so neither side takes anything the other made.

Every matrix product goes through ``mm``: float32 at ``HIGHEST`` precision
for the reference, or an fp8 (e4m3, one scale per tensor) product in
forward and backward for the control.  The gradient is summed in blocks of
rows so that long sequences and large vocabularies fit one chip.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, jax.Array]

LAYER_KEYS = ("input_layernorm", "q_proj", "q_bias", "k_proj", "k_bias",
              "v_proj", "v_bias", "o_proj", "post_attention_layernorm",
              "gate_proj", "up_proj", "down_proj")
LOGIT_CHUNK = 512          # positions per block of logits


def dims(cfg: Dict):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    dh = cfg.get("head_dim") or d // h
    return d, h, kv, dh, cfg["intermediate_size"], cfg["num_hidden_layers"]


def shapes(cfg: Dict) -> Dict[str, tuple]:
    d, h, kv, dh, ff, n = dims(cfg)
    out = {"embed": (cfg["vocab_size"], d), "final_norm": (d,),
           "input_layernorm": (n, d), "post_attention_layernorm": (n, d),
           "q_proj": (n, d, h * dh), "q_bias": (n, h * dh),
           "k_proj": (n, d, kv * dh), "k_bias": (n, kv * dh),
           "v_proj": (n, d, kv * dh), "v_bias": (n, kv * dh),
           "o_proj": (n, h * dh, d), "gate_proj": (n, d, ff),
           "up_proj": (n, d, ff), "down_proj": (n, ff, d)}
    if not cfg["tie_word_embeddings"]:
        out["lm_head"] = (d, cfg["vocab_size"])
    return out


def init_weights(cfg: Dict, key: jax.Array) -> Params:
    """Random weights: projections N(0, 1/d_in), the output projections
    further scaled by 1/sqrt(2 * layers), embeddings and biases N(0, 0.02^2),
    norm scales 1.  Call it under ``jax.jit``."""
    dtype = jnp.dtype(cfg["torch_dtype"])
    n_layers = cfg["num_hidden_layers"]
    out = {}
    for i, (name, shp) in enumerate(sorted(shapes(cfg).items())):
        k = jax.random.fold_in(key, i)
        if name.endswith("layernorm") or name == "final_norm":
            w = jnp.ones(shp, jnp.float32)
        elif name in ("embed",) or name.endswith("_bias"):
            w = 0.02 * jax.random.normal(k, shp, jnp.float32)
        else:
            std = 1.0 / math.sqrt(shp[-2])
            if name in ("o_proj", "down_proj"):
                std /= math.sqrt(2 * n_layers)
            w = std * jax.random.normal(k, shp, jnp.float32)
        out[name] = w.astype(dtype)
    return out


# --------------------------------------------------------------------------- #
# matrix products: the reference's precision and the control's
# --------------------------------------------------------------------------- #
def mm_f32(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


_E4M3_MAX = 448.0


def _fp8(x: jax.Array) -> jax.Array:
    """Round to float8 e4m3 with one scale for the tensor."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _E4M3_MAX
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * s


@jax.custom_vjp
def _fp8_operand(x):
    return _fp8(x)


_fp8_operand.defvjp(lambda x: (_fp8(x), None), lambda _, g: (g,))


@jax.custom_vjp
def _fp8_cotangent(x):
    return x


_fp8_cotangent.defvjp(lambda x: (x, None), lambda _, g: (_fp8(g),))


def mm_fp8(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    """fp8 product: both operands in forward, and the incoming gradient in
    backward, rounded to e4m3; accumulation in float32."""
    return _fp8_cotangent(mm_f32(spec, _fp8_operand(a), _fp8_operand(b)))


MM = {"f32": mm_f32, "fp8": mm_fp8}


# --------------------------------------------------------------------------- #
# the model
# --------------------------------------------------------------------------- #
def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
    """x: [B, S, heads, dh]; rotate-half rotary embedding."""
    s, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh)
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(x, p, cfg, mm):
    d, h, kv, dh, ff, _ = dims(cfg)
    b, s, _ = x.shape
    eps = cfg["rms_norm_eps"]
    y = rms_norm(x, p["input_layernorm"], eps)
    q = (mm("bsd,de->bse", y, p["q_proj"]) + p["q_bias"]).reshape(b, s, h, dh)
    k = (mm("bsd,de->bse", y, p["k_proj"]) + p["k_bias"]).reshape(b, s, kv, dh)
    v = (mm("bsd,de->bse", y, p["v_proj"]) + p["v_bias"]).reshape(b, s, kv, dh)
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    k = jnp.repeat(k, h // kv, axis=2)
    v = jnp.repeat(v, h // kv, axis=2)
    scores = mm("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh)
    causal = np.tril(np.ones((s, s), dtype=bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    attn = mm("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + mm("bse,ed->bsd", attn.reshape(b, s, h * dh), p["o_proj"])
    y = rms_norm(x, p["post_attention_layernorm"], eps)
    act = jax.nn.silu(mm("bsd,df->bsf", y, p["gate_proj"]))
    x = x + mm("bsf,fd->bsd", act * mm("bsd,df->bsf", y, p["up_proj"]),
               p["down_proj"])
    return x


def loss_sum(params: Params, tokens, labels, cfg: Dict, mm: Callable):
    """Sum over every token of the next-token cross-entropy."""
    x = params["embed"][tokens]
    stacked = {k: params[k] for k in LAYER_KEYS}

    @jax.checkpoint
    def body(x, p):
        return layer(x, p, cfg, mm), None

    x, _ = jax.lax.scan(body, x, stacked)
    x = rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])
    head = params.get("lm_head")
    b, s, d = x.shape
    chunk = min(LOGIT_CHUNK, s)
    xs = x.reshape(b, s // chunk, chunk, d).transpose(1, 0, 2, 3)
    ls = labels.reshape(b, s // chunk, chunk).transpose(1, 0, 2)

    @jax.checkpoint
    def xent(total, xl):
        xc, lc = xl
        if head is None:
            logits = mm("bsd,vd->bsv", xc, params["embed"])
        else:
            logits = mm("bsd,dv->bsv", xc, head)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return total + jnp.sum(lse - gold), None

    total, _ = jax.lax.scan(xent, jnp.zeros((), jnp.float32), (xs, ls))
    return total


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _grad_block(params, tokens, labels, cfg_items, precision):
    cfg = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_sum)(params, tokens, labels, cfg,
                                            MM[precision])


@functools.partial(jax.jit, static_argnames=("lr", "gamma", "dtype"),
                   donate_argnums=(1,))
def _update(params, history, grads, n_tokens, lr, gamma, dtype):
    new_h = jax.tree.map(lambda g, h: -lr * (g / n_tokens) + gamma * h,
                         grads, history)
    new_p = jax.tree.map(lambda p, h: (p.astype(jnp.float32) + h).astype(dtype),
                         params, new_h)
    return new_p, new_h


def train_steps(cfg: Dict, params: Params, batches: Sequence[Dict],
                *, lr: float, gamma: float, rows: int,
                precision: str = "f32", device=None) -> Dict:
    """Run ``len(batches)`` momentum-SGD steps from ``params`` (stored in
    the configuration's dtype):  h <- -lr * g + gamma * h;  w <- w + h,
    with h kept in float32 and w rounded back to the stored dtype.

    Each batch's gradient is the mean over all its tokens, summed in blocks
    of ``rows`` rows.  Returns the loss of every step, the first step's
    gradient and the parameters after the last step (all on ``device``).
    """
    cfg_items = tuple(sorted((k, v) for k, v in cfg.items()
                             if isinstance(v, (int, float, str, bool))))
    dtype = jnp.dtype(cfg["torch_dtype"])
    put = (lambda x: jax.device_put(x, device)) if device is not None else jnp.asarray
    history = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    losses: List[float] = []
    first_grad: Optional[Params] = None
    for batch in batches:
        tokens, labels = batch["tokens"], batch["labels"]
        n_tok = tokens.size
        params32 = jax.tree.map(lambda p: p.astype(jnp.float32), params)
        total, grads = 0.0, None
        for r in range(0, tokens.shape[0], rows):
            loss, g = _grad_block(params32, put(tokens[r:r + rows]),
                                  put(labels[r:r + rows]), cfg_items,
                                  precision)
            total += float(loss)
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        del params32
        losses.append(total / n_tok)
        if first_grad is None:
            first_grad = jax.tree.map(lambda g: g / n_tok, grads)
        params, history = _update(params, history, grads, float(n_tok),
                                  lr=lr, gamma=gamma, dtype=dtype)
    return {"losses": losses, "grad": first_grad, "params": params}
