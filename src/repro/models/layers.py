"""Core transformer building blocks, pure JAX.

Conventions
-----------
* Params are nested dicts of jnp arrays; every module provides
  ``init_<module>(rng, cfg) -> params`` and a pure ``apply`` function.
* Weights are stored in ``cfg.dtype`` (default bf16); numerically sensitive
  reductions (norms, softmax, logsumexp) run in f32.
* Head axes carry logical sharding names via ``logical_specs`` companions
  (see models/sharding.py).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import ops
from repro.kernels.decode_attention import NO_WINDOW


# ---------------------------------------------------------------------------
# config
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | rwkv | hybrid | vlm | encdec
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    # attention
    qkv_bias: bool = False
    attn_softcap: Optional[float] = None
    final_softcap: Optional[float] = None
    sliding_window: Optional[int] = None      # window for local layers
    local_global_alternating: bool = False    # gemma2: even layers local
    rope_theta: float = 10_000.0
    # mlp
    mlp_activation: str = "silu"              # silu->SwiGLU, gelu->GeGLU
    mlp_gated: bool = True                    # False: plain 2-matrix MLP
    use_layernorm: bool = False               # LN instead of RMSNorm
    use_post_norms: bool = False              # gemma2 sandwich norms
    use_rope: bool = True                     # False: absolute positions
    # moe
    num_experts: int = 0
    top_k: int = 0
    num_shared_experts: int = 0
    moe_dense_residual_ff: int = 0            # arctic: parallel dense FFN
    moe_capacity_factor: float = 2.0
    expert_pad_to: int = 0                    # physical expert-table pad so
                                              # E divides the EP group (the
                                              # padded experts get -inf
                                              # router logits, never routed)
    # ssm / hybrid
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_expand: int = 2
    attn_every: int = 0                       # zamba2: shared attn cadence
    # encdec
    encoder_layers: int = 0
    # vlm
    vision_feature_dim: int = 0
    num_patches: int = 0
    # misc
    norm_eps: float = 1e-6
    tie_embeddings: bool = True
    dtype: Any = jnp.bfloat16
    # checkpointing / perf knobs (hillclimb surface)
    remat_policy: str = "dots"                # none | dots | full
    scan_layers: bool = True
    attn_q_chunk: int = 512                   # flash-style Q blocking
    attn_unroll_chunks: bool = False          # dry-run: unroll so XLA's
                                              # static cost model sees all
                                              # chunks (while bodies are
                                              # counted once otherwise)
    kv_cache_quant: bool = False              # fp8(e4m3) KV cache storage
                                              # (decode memory-term lever)
    window_sized_cache: bool = False          # gemma2: local layers keep a
                                              # window-sized ring cache
                                              # instead of full seq

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim


# ---------------------------------------------------------------------------
# initialisers
# ---------------------------------------------------------------------------


def _dense_init(rng, shape, dtype, in_axis_size=None):
    fan_in = in_axis_size if in_axis_size is not None else shape[0]
    scale = 1.0 / math.sqrt(max(1, fan_in))
    return (jax.random.normal(rng, shape, jnp.float32) * scale).astype(dtype)


def _embed_init(rng, shape, dtype):
    return (jax.random.normal(rng, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * lax.rsqrt(var + eps)
    return (out * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    out = (xf - mu) * lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def init_norm(cfg: ModelConfig):
    if cfg.use_layernorm:
        return {
            "scale": jnp.ones((cfg.d_model,), cfg.dtype),
            "bias": jnp.zeros((cfg.d_model,), cfg.dtype),
        }
    return {"scale": jnp.zeros((cfg.d_model,), cfg.dtype)}


def apply_norm(params, x, cfg: ModelConfig):
    if cfg.use_layernorm:
        return layer_norm(x, params["scale"], params["bias"], cfg.norm_eps)
    return rms_norm(x, params["scale"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)  # (head_dim/2,)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: broadcastable to (..., seq)."""
    freqs = rope_frequencies(x.shape[-1], theta)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    cos = jnp.cos(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def init_attention(rng, cfg: ModelConfig):
    k = jax.random.split(rng, 4)
    p = {
        "wq": _dense_init(k[0], (cfg.d_model, cfg.num_heads, cfg.head_dim), cfg.dtype),
        "wk": _dense_init(k[1], (cfg.d_model, cfg.num_kv_heads, cfg.head_dim), cfg.dtype),
        "wv": _dense_init(k[2], (cfg.d_model, cfg.num_kv_heads, cfg.head_dim), cfg.dtype),
        "wo": _dense_init(
            k[3], (cfg.num_heads, cfg.head_dim, cfg.d_model), cfg.dtype,
            in_axis_size=cfg.q_dim,
        ),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.num_heads, cfg.head_dim), cfg.dtype)
        p["bk"] = jnp.zeros((cfg.num_kv_heads, cfg.head_dim), cfg.dtype)
        p["bv"] = jnp.zeros((cfg.num_kv_heads, cfg.head_dim), cfg.dtype)
    return p


def _soft_cap(logits: jax.Array, cap: Optional[float]) -> jax.Array:
    if cap is None:
        return logits
    return cap * jnp.tanh(logits / cap)


@dataclasses.dataclass(frozen=True)
class MaskSpec:
    """Lazy attention-mask description. Masks are generated per Q-chunk so
    an (Sq, Sk) boolean is never materialised at long context.

    kind:
      * "causal"   — query i attends to kpos <= q_offset + i
      * "full"     — bidirectional (encoder)
      * "lengths"  — decode: kpos <= lengths[b] (lengths is (B,))
    ``window``: additionally restrict to kpos > qpos - window.
    """
    kind: str = "causal"
    window: Optional[int] = None
    q_offset: int = 0

    def block(self, sq: int, sk: int, q_start, lengths=None) -> jax.Array:
        kpos = jnp.arange(sk)[None, :]
        if self.kind == "full":
            ok = jnp.ones((sq, sk), bool)[None, None]
        elif self.kind == "causal":
            qpos = jnp.arange(sq)[:, None] + q_start + self.q_offset
            ok = kpos <= qpos
            if self.window is not None:
                ok = ok & (kpos > qpos - self.window)
            ok = ok[None, None]
        elif self.kind == "lengths":
            ok = kpos <= lengths[:, None]
            if self.window is not None:
                ok = ok & (kpos > lengths[:, None] - self.window)
            ok = jnp.broadcast_to(ok[:, None, None, :], (lengths.shape[0], 1, sq, sk))
        elif self.kind == "ring":
            # window-ring decode cache: slots [0, min(lengths+1, sk)) hold
            # the last tokens; once wrapped, every slot is valid.
            ok = (kpos <= lengths[:, None]) | (lengths[:, None] + 1 >= sk)
            ok = jnp.broadcast_to(ok[:, None, None, :],
                                  (lengths.shape[0], 1, sq, sk))
        elif self.kind == "chunk":
            # chunked prefill: query i of this chunk sits at absolute
            # position lengths[b] + q_start + i (lengths = per-request
            # already-prefilled token count).
            qpos = lengths[:, None, None] + q_start + jnp.arange(sq)[None, :, None]
            ok = kpos[None] <= qpos
            if self.window is not None:
                ok = ok & (kpos[None] > qpos - self.window)
            ok = ok[:, None]
        else:
            raise ValueError(self.kind)
        return ok


def attention_scores(
    q: jax.Array,              # (B, Sq, Hq, D) — rope already applied
    k: jax.Array,              # (B, Sk, Hkv, D)
    v: jax.Array,              # (B, Sk, Hkv, D)
    mask: MaskSpec,
    *,
    attn_softcap: Optional[float] = None,
    lengths: Optional[jax.Array] = None,
    q_chunk: int = 512,
    unroll: bool = False,
) -> jax.Array:
    """XLA reference attention with flash-style Q chunking at long context;
    the Pallas kernels replace this on the serving hot path.
    Returns (B, Sq, Hq, D)."""
    b, sq, hq, d = q.shape
    if sq <= q_chunk:
        return _attn_block(q, k, v, mask, 0, attn_softcap, lengths)

    n = sq // q_chunk
    assert n * q_chunk == sq, f"Sq={sq} not a multiple of {q_chunk}"
    qc = q.reshape(b, n, q_chunk, hq, d).transpose(1, 0, 2, 3, 4)

    def body(_, args):
        i, qi = args
        out = _attn_block(qi, k, v, mask, i * q_chunk, attn_softcap, lengths)
        return None, out

    _, outs = lax.scan(body, None, (jnp.arange(n), qc),
                       unroll=n if unroll else 1)
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, sq, hq, d)


def _attn_block(q, k, v, mask: MaskSpec, q_start, attn_softcap, lengths):
    """bf16 x bf16 -> f32 dots via preferred_element_type: no materialised
    f32 copy of the KV cache (MXU-native mixed precision); softmax in f32;
    probabilities cast back to the KV dtype for the AV matmul (flash-attn
    convention)."""
    b, sq, hq, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    group = hq // hkv
    qg = q.reshape(b, sq, hkv, group, d)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32)
    logits = logits * (1.0 / math.sqrt(d))
    logits = _soft_cap(logits, attn_softcap)
    m = mask.block(sq, sk, q_start, lengths)       # (B|1, 1, sq, sk)
    logits = jnp.where(m[:, :, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, sq, hq, d).astype(q.dtype)


def apply_attention(
    params,
    x: jax.Array,              # (B, S, d_model)
    cfg: ModelConfig,
    *,
    positions: jax.Array,      # (B, S) absolute positions
    mask: MaskSpec,
    kv_cache: Optional[tuple[jax.Array, jax.Array]] = None,
    cache_positions: Optional[jax.Array] = None,  # (B,) write offsets
    lengths: Optional[jax.Array] = None,          # (B,) for "lengths" masks
    rope: bool = True,
    cross_kv: Optional[tuple[jax.Array, jax.Array]] = None,
    layer=None,                # index into a stacked kv_cache
):
    """Returns (out, new_cache).

    * no cache: self-attention over x (prefill / train).
    * kv_cache (B, Smax, Hkv, D), one layer's: write new K/V at
      ``cache_positions``, attend over the cache (decode).
    * kv_cache (L, B, Hkv, Smax, D) with ``layer``: the model's stacked,
      head-major cache. The new rows are written into layer ``layer`` in
      place, that layer is read back and attended over; the whole stacked
      pair is returned. A decode step on one device reads each row's live
      positions in place (``kernels/decode_attention.py``); under a mesh
      of several devices, which may shard the cache, XLA attends.
    * cross_kv: attend over fixed K/V (encoder-decoder cross attention);
      no Q/K rope, no cache update.
    """
    b, s, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    if cfg.qkv_bias:
        q = q + params["bq"]

    if cross_kv is not None:
        k_all, v_all = cross_kv
        out = attention_scores(q, k_all, v_all, mask,
                               attn_softcap=cfg.attn_softcap, lengths=lengths,
                               q_chunk=cfg.attn_q_chunk,
                               unroll=cfg.attn_unroll_chunks)
        out = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
        return out, None

    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if cfg.qkv_bias:
        k = k + params["bk"]
        v = v + params["bv"]
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    if kv_cache is not None and layer is not None:
        k_cache = _stacked_cache_write(kv_cache[0], k, layer, cache_positions)
        v_cache = _stacked_cache_write(kv_cache[1], v, layer, cache_positions)
        new_cache = (k_cache, v_cache)
        if s == 1 and mask.kind == "lengths" and _one_device():
            # decode: read each row's live positions in place (kernel)
            window = NO_WINDOW if mask.window is None else mask.window
            out = ops.decode_attention_op(
                q[:, 0], k_cache, v_cache, layer, window, lengths,
                softcap=cfg.attn_softcap)[:, None]
            out = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
            return out, new_cache
        k_all = _layer_view(k_cache, layer)
        v_all = _layer_view(v_cache, layer)
    elif kv_cache is not None:
        k_cache, v_cache = kv_cache
        k_cache = _cache_write(k_cache, k, cache_positions)
        v_cache = _cache_write(v_cache, v, cache_positions)
        k_all, v_all = k_cache, v_cache
        new_cache = (k_cache, v_cache)
    else:
        k_all, v_all = k, v
        new_cache = (k, v)
    if k_all.dtype != q.dtype:          # quantised (fp8) cache storage
        k_all = k_all.astype(q.dtype)
        v_all = v_all.astype(q.dtype)

    out = attention_scores(q, k_all, v_all, mask,
                           attn_softcap=cfg.attn_softcap, lengths=lengths,
                           q_chunk=cfg.attn_q_chunk,
                           unroll=cfg.attn_unroll_chunks)
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return out, new_cache


def _one_device() -> bool:
    """No mesh of several devices is set, so the cache lies whole on one
    device. A program that shards the cache sets its mesh
    (``jax.set_mesh``), and the compiler could partition the kernel only
    by gathering the cache."""
    mesh = jax.sharding.get_abstract_mesh()
    return mesh.empty or mesh.size == 1


def compute_kv(params, x, cfg: ModelConfig, positions=None, rope=False):
    """K/V projection only (whisper cross-attn precompute)."""
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if cfg.qkv_bias:
        k = k + params["bk"]
        v = v + params["bv"]
    if rope:
        k = apply_rope(k, positions, cfg.rope_theta)
    return k, v


def _cache_write(cache: jax.Array, new: jax.Array, write_pos: jax.Array) -> jax.Array:
    """cache: (B, Smax, H, D); new: (B, Snew, H, D); write_pos: (B,)."""

    def upd(c, n, p):
        return lax.dynamic_update_slice_in_dim(c, n.astype(c.dtype), p, axis=0)

    return jax.vmap(upd)(cache, new, write_pos)


def _stacked_cache_write(cache: jax.Array, new: jax.Array, layer,
                         write_pos: jax.Array) -> jax.Array:
    """cache: (L, B, H, Smax, D); new: (B, Snew, H, D); write_pos: (B,).
    Row ``b``'s tokens go to ``[layer, b, :, write_pos[b]:+Snew]``, in
    place on a scan carry or a donated buffer. Callers keep every write
    inside the cache (the executor pads chunks left).

    A decode step (Snew == 1) writes its B x H head_dim rows with one
    scatter; a prefill chunk writes each row's (H, Snew, D) block with its
    own ``dynamic_update_slice``. On a v5e (qwen2-1.5b, 16 x 8192) the
    per-row loop made a decode step 0.45 ms slower, and a scatter of a
    2048-token chunk's rows took 12 ms for each of K and V. The scatter
    index names every axis but head_dim (a wider window makes the TPU
    compiler relay the cache, or loop over the windows), in (b, h) order:
    the sorted mark must be true, as the TPU's scatter relies on it."""
    rows = new.transpose(0, 2, 1, 3).astype(cache.dtype)     # (B, H, Snew, D)
    b, h, s, _ = rows.shape
    if s == 1:
        idx = (layer, jnp.arange(b)[:, None], jnp.arange(h)[None, :],
               write_pos[:, None])
        return cache.at[idx].set(rows[:, :, 0], indices_are_sorted=True,
                                 unique_indices=True)
    for i in range(b):
        cache = lax.dynamic_update_slice(
            cache, rows[i][None, None], (layer, i, 0, write_pos[i], 0))
    return cache


def _layer_view(cache: jax.Array, layer) -> jax.Array:
    """Layer ``layer`` of a stacked (L, B, H, Smax, D) cache, as the
    (B, Smax, H, D) that ``attention_scores`` reads."""
    return lax.dynamic_index_in_dim(cache, layer, 0, keepdims=False) \
        .transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# dense MLP
# ---------------------------------------------------------------------------


def init_mlp(rng, cfg: ModelConfig, d_ff: Optional[int] = None):
    d_ff = d_ff or cfg.d_ff
    k = jax.random.split(rng, 3)
    p = {
        "w_up": _dense_init(k[1], (cfg.d_model, d_ff), cfg.dtype),
        "w_down": _dense_init(k[2], (d_ff, cfg.d_model), cfg.dtype),
    }
    if cfg.mlp_gated:
        p["w_gate"] = _dense_init(k[0], (cfg.d_model, d_ff), cfg.dtype)
    return p


def _activate(x: jax.Array, kind: str) -> jax.Array:
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x, approximate=True)
    raise ValueError(kind)


def apply_mlp(params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    u = jnp.einsum("bsd,df->bsf", x, params["w_up"])
    if "w_gate" in params:
        g = _activate(jnp.einsum("bsd,df->bsf", x, params["w_gate"]),
                      cfg.mlp_activation)
        u = g * u
    else:
        u = _activate(u, cfg.mlp_activation)
    return jnp.einsum("bsf,fd->bsd", u, params["w_down"])


# ---------------------------------------------------------------------------
# embeddings / unembedding
# ---------------------------------------------------------------------------


def init_embedding(rng, cfg: ModelConfig):
    p = {"table": _embed_init(rng, (cfg.vocab_size, cfg.d_model), cfg.dtype)}
    if not cfg.tie_embeddings:
        p["unembed"] = _embed_init(
            jax.random.fold_in(rng, 1), (cfg.vocab_size, cfg.d_model), cfg.dtype
        )
    return p


def embed(params, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    x = params["table"][tokens]
    return x * jnp.asarray(math.sqrt(cfg.d_model), cfg.dtype)


def unembed(params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    table = params.get("unembed", params["table"])
    logits = jnp.einsum("bsd,vd->bsv", x, table).astype(jnp.float32)
    return _soft_cap(logits, cfg.final_softcap)


def softmax_xent(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Sharding-friendly CE: never materialises probabilities.

    logits (B, S, V) f32, labels (B, S) int32 -> scalar mean loss.
    Reductions over V lower to small per-token all-reduces when V is
    sharded (GSPMD handles the sharded-axis reduction)."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=logits.dtype)
    true_logit = jnp.sum(logits * onehot, axis=-1)
    return jnp.mean(lse - true_logit)
