"""Plain reference of a dense decoder-only LM (Qwen2, InternLM2), float32.

Imports nothing of the program. It runs each sequence whole, causally,
with no cache, no kernels and no batching across requests, over the
weights ``init_params`` makes from the seed: the benchmark makes them once
for the program to serve, and again for the reference after the
program's state is freed. The layout is the served one: norms hold
``scale`` with weight ``1 + scale``, ``wq`` is ``(d, heads, head_dim)``,
blocks are stacked over depth.

Departures from the published descriptions, shared with the served model:

* token embeddings are multiplied by ``sqrt(hidden_size)``. With an
  untied output head (InternLM2) that is only a rescaled embedding table.
  With a tied head (Qwen2) the logits come out ``1/sqrt(hidden_size)`` of
  Qwen2's for the same table: the ranking is the same, every gap shrinks
  by that factor.
* RMSNorm weights are stored as ``scale`` and applied as ``1 + scale``:
  a reparametrisation.

``gaps`` returns, at every position, the largest logit and the logit of
each given target token, so a caller reads by how much a served token lies
below the reference's best. ``low=True`` is the control: every
matmul input (weights and activations) rounded to float8 e4m3 with a
per-tensor scale, accumulation in float32.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

Q_BLOCK = 512          # query rows per attention block
V_BLOCK = 512          # positions per block of output logits
FP8_MAX = 448.0        # largest finite float8_e4m3fn
QKV_BIAS = {"qwen2": True, "internlm2": False}   # by the config's model_type


class Spec(NamedTuple):
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    qkv_bias: bool
    tied: bool
    rope_theta: float
    eps: float


def spec_of(cfg: dict) -> Spec:
    """Reference shape from a configuration file (HF key names)."""
    heads = cfg["num_attention_heads"]
    return Spec(
        layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        heads=heads, kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim", cfg["hidden_size"] // heads),
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        qkv_bias=QKV_BIAS[cfg["model_type"]],
        tied=bool(cfg["tie_word_embeddings"]),
        rope_theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]))


# --------------------------------------------------------------- weights
EMBED_STD = 0.02       # HF initializer_range of both source configs


def init_params(key, s: Spec, dtype=jnp.bfloat16):
    """The benchmark's random weights for one seed, in the served layout
    (blocks stacked over depth; the program serves these same arrays).

    Matrices are N(0, 1/fan_in), q/k/v biases N(0, 0.02^2), RMSNorm
    scales 0 (weight 1). The embedding table is N(0, (0.02/sqrt(d))^2):
    times the served model's ``sqrt(d)`` input scale, the residual stream
    starts at the configs' initializer range. (At std 0.02 itself, with
    Qwen2's tied head, every position's own token would out-score the
    vocabulary and the output would only repeat its input.) An untied
    head is N(0, 0.02^2)."""
    d, L = s.d_model, s.layers
    q, kv = (s.heads, s.head_dim), (s.kv_heads, s.head_dim)
    shapes = {
        ("attn", "wq"): ((L, d) + q, d), ("attn", "wk"): ((L, d) + kv, d),
        ("attn", "wv"): ((L, d) + kv, d),
        ("attn", "wo"): ((L,) + q + (d,), s.heads * s.head_dim),
        ("mlp", "w_gate"): ((L, d, s.d_ff), d),
        ("mlp", "w_up"): ((L, d, s.d_ff), d),
        ("mlp", "w_down"): ((L, s.d_ff, d), s.d_ff),
    }
    if s.qkv_bias:
        shapes[("attn", "bq")] = ((L,) + q, None)
        shapes[("attn", "bk")] = ((L,) + kv, None)
        shapes[("attn", "bv")] = ((L,) + kv, None)
    keys = jax.random.split(key, len(shapes) + 2)
    blocks = {"attn": {}, "mlp": {},
              "norm_attn": {"scale": jnp.zeros((L, d), dtype)},
              "norm_mlp": {"scale": jnp.zeros((L, d), dtype)}}
    for k, ((group, name), (shape, fan_in)) in zip(keys, shapes.items()):
        std = EMBED_STD if fan_in is None else 1.0 / math.sqrt(fan_in)
        blocks[group][name] = (jax.random.normal(k, shape, jnp.float32)
                               * std).astype(dtype)
    embed = {"table": (jax.random.normal(keys[-2], (s.vocab, d), jnp.float32)
                       * (EMBED_STD / math.sqrt(d))).astype(dtype)}
    if not s.tied:
        embed["unembed"] = (jax.random.normal(keys[-1], (s.vocab, d),
                                              jnp.float32)
                            * EMBED_STD).astype(dtype)
    return {"embed": embed, "blocks": blocks,
            "final_norm": {"scale": jnp.zeros((d,), dtype)}}


# ---------------------------------------------------------------- forward
def _fp8(x):
    """Round to float8 e4m3 under a per-tensor scale, back in float32."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    scale = FP8_MAX / amax
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def _dot(spec_str, a, b, low):
    if low:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec_str, a, b, precision=lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))


def _rope(x, pos, theta):
    """Rotate-half RoPE. x (T, H, D), pos (T,)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None].astype(jnp.float32) * inv          # (T, D/2)
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, low):
    """Causal GQA over one whole sequence, in blocks of query rows.
    q (T, H, D); k, v (T, Hkv, D)."""
    t, h, d = q.shape
    g = h // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    kpos = jnp.arange(t)

    def block(i):
        qb = lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        s = _dot("qhd,khd->hqk", qb, k, low) / math.sqrt(d)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _dot("hqk,khd->qhd", p, v, low)

    out = lax.map(block, jnp.arange(t // Q_BLOCK))
    return out.reshape(t, h, d)


def hidden(params, tokens, s: Spec, low: bool = False):
    """Final normed hidden states (T, d) of one sequence; T a multiple of
    ``Q_BLOCK``. Padding after the real tokens leaves them unchanged."""
    f32 = jnp.float32
    table = params["embed"]["table"].astype(f32)
    x = table[tokens] * math.sqrt(s.d_model)
    pos = jnp.arange(tokens.shape[0])

    def layer(x, bp):
        bp = jax.tree.map(lambda a: a.astype(f32), bp)
        a = bp["attn"]
        h = _rms(x, bp["norm_attn"]["scale"], s.eps)
        q = _dot("td,dhk->thk", h, a["wq"], low)
        k = _dot("td,dhk->thk", h, a["wk"], low)
        v = _dot("td,dhk->thk", h, a["wv"], low)
        if s.qkv_bias:
            q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
        q, k = _rope(q, pos, s.rope_theta), _rope(k, pos, s.rope_theta)
        o = _attention(q, k, v, low)
        x = x + _dot("thk,hkd->td", o, a["wo"], low)
        m = bp["mlp"]
        h = _rms(x, bp["norm_mlp"]["scale"], s.eps)
        u = jax.nn.silu(_dot("td,df->tf", h, m["w_gate"], low)) \
            * _dot("td,df->tf", h, m["w_up"], low)
        return x + _dot("tf,fd->td", u, m["w_down"], low), None

    x, _ = lax.scan(layer, x, params["blocks"])
    return _rms(x, params["final_norm"]["scale"], s.eps)


def gaps(params, tokens, targets, s: Spec, low: bool = False):
    """tokens (T,), targets (K, T) -> (best (T,), target logits (K, T),
    argmax (T,)): at each position the largest logit, the logit of each
    target token there, and the token holding the largest."""
    x = hidden(params, tokens, s, low)
    emb = params["embed"]
    head = emb.get("unembed", emb["table"]).astype(jnp.float32)
    t = tokens.shape[0]

    def block(i):
        xb = lax.dynamic_slice_in_dim(x, i * V_BLOCK, V_BLOCK, 0)
        logits = _dot("td,vd->tv", xb, head, low)
        tb = lax.dynamic_slice_in_dim(targets, i * V_BLOCK, V_BLOCK, 1)
        picked = jnp.take_along_axis(logits[None], tb[..., None], -1)[..., 0]
        return logits.max(-1), picked, jnp.argmax(logits, -1)

    best, picked, arg = lax.map(block, jnp.arange(t // V_BLOCK))
    k = targets.shape[0]
    return (best.reshape(t), picked.transpose(1, 0, 2).reshape(k, t),
            arg.reshape(t).astype(jnp.int32))
