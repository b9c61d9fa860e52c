"""Decoder-only transformer LM (dense + MoE + VLM-backbone variants).

One homogeneous block is scanned over depth. Per-layer heterogeneity
(gemma2 local/global alternation) is expressed with *scanned arrays*
(per-layer attention window), so a single scan covers every family and the
HLO stays O(1) in depth.

The serving cache is one stacked, head-major pair (L, B, Hkv, S, D) per K
and V. Prefill and decode carry it through the layer scan: each layer
writes its new rows into it in place and reads its own layer back, so a
step moves only the rows it writes and the layers it attends over.
Window-sized ring caches (gemma2, unrolled) stay per-layer (B, S, Hkv, D).

Public entry points (all pure):
  init_lm(rng, cfg)                                  -> params
  init_cache(cfg, batch, max_len, dtype)             -> cache pytree
  forward_train(params, tokens, cfg, ep)             -> logits (B, S, V)
  prefill(params, cache, tokens, lengths, cfg, ep)   -> (last_logits, cache)
  prefill_chunk(params, cache, chunk, starts, cfg)   -> (last_logits, cache)
  decode(params, cache, tokens, lengths, cfg, ep)    -> (logits, cache)
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.decode_attention import NO_WINDOW
from repro.models import layers as L
from repro.models import moe as M
from repro.models.layers import MaskSpec, ModelConfig


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_block(rng, cfg: ModelConfig):
    k = jax.random.split(rng, 6)
    p = {
        "norm_attn": L.init_norm(cfg),
        "attn": L.init_attention(k[0], cfg),
        "norm_mlp": L.init_norm(cfg),
    }
    if cfg.use_post_norms:
        p["norm_attn_post"] = L.init_norm(cfg)
        p["norm_mlp_post"] = L.init_norm(cfg)
    if cfg.is_moe:
        p["moe"] = M.init_moe(k[1], cfg)
        if cfg.moe_dense_residual_ff:
            p["mlp"] = L.init_mlp(k[2], cfg, d_ff=cfg.moe_dense_residual_ff)
    else:
        p["mlp"] = L.init_mlp(k[2], cfg)
    return p


def init_lm(rng, cfg: ModelConfig):
    k = jax.random.split(rng, 3)
    blocks = jax.vmap(lambda r: _init_block(r, cfg))(
        jax.random.split(k[0], cfg.num_layers)
    )
    p = {
        "embed": L.init_embedding(k[1], cfg),
        "blocks": blocks,
        "final_norm": L.init_norm(cfg),
    }
    if cfg.vision_feature_dim:
        p["vision_proj"] = L._dense_init(
            k[2], (cfg.vision_feature_dim, cfg.d_model), cfg.dtype
        )
    return p


def layer_windows_py(cfg: ModelConfig) -> list:
    if cfg.local_global_alternating and cfg.sliding_window:
        return [cfg.sliding_window if i % 2 == 0 else NO_WINDOW
                for i in range(cfg.num_layers)]
    if cfg.sliding_window:
        return [cfg.sliding_window] * cfg.num_layers
    return [NO_WINDOW] * cfg.num_layers


def layer_windows(cfg: ModelConfig) -> jnp.ndarray:
    """Per-layer sliding windows as a scanned i32 array; NO_WINDOW = global.
    gemma2 convention: even layers local, odd layers global."""
    return jnp.asarray(layer_windows_py(cfg), jnp.int32)


def _cache_dtype(cfg: ModelConfig, dtype=None):
    if dtype is not None:
        return dtype
    if cfg.kv_cache_quant:
        return jnp.float8_e4m3fn
    return cfg.dtype


def _use_ring(cfg: ModelConfig) -> bool:
    return cfg.window_sized_cache and cfg.local_global_alternating \
        and not cfg.scan_layers


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None):
    """{"k", "v"}: each (num_layers, batch, num_kv_heads, max_len,
    head_dim), head-major with the slot on axis 1; or, for a window-sized
    ring cache, per-layer tuples of (batch, S, num_kv_heads, head_dim)."""
    dtype = _cache_dtype(cfg, dtype)
    if _use_ring(cfg):
        # per-layer cache: local layers keep only a window-sized ring
        ks, vs = [], []
        for w in layer_windows_py(cfg):
            s = min(max_len, w)
            shape = (batch, s, cfg.num_kv_heads, cfg.head_dim)
            ks.append(jnp.zeros(shape, dtype))
            vs.append(jnp.zeros(shape, dtype))
        return {"k": tuple(ks), "v": tuple(vs)}
    shape = (cfg.num_layers, batch, cfg.num_kv_heads, max_len, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_spec(cfg: ModelConfig, batch: int, max_len: int, dtype=None):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
        jax.eval_shape(lambda: init_cache(cfg, batch, max_len, dtype)),
    )


# ---------------------------------------------------------------------------
# block
# ---------------------------------------------------------------------------


def _block_fn(
    bp,
    x,
    cfg: ModelConfig,
    *,
    positions,
    mask: MaskSpec,
    window,
    kv=None,
    layer=None,
    cache_positions=None,
    lengths=None,
    ep: Optional[M.EPInfo] = None,
    ring: bool = False,
):
    """One transformer block. ``window`` is a traced per-layer scalar.
    ``kv`` is the stacked cache with ``layer`` this block's index in it,
    or one layer's cache (``layer`` None).
    ``ring``: this layer's cache is a window-sized ring buffer (decode-only;
    the ring holds exactly the last ``ring_size`` tokens so the window mask
    reduces to slot-validity)."""
    if ring:
        ring_size = kv[0].shape[1]
        mask = MaskSpec(kind="ring")
        cache_positions = jnp.mod(cache_positions, ring_size)
    else:
        mask = MaskSpec(kind=mask.kind, window=window, q_offset=mask.q_offset)
    h = L.apply_norm(bp["norm_attn"], x, cfg)
    attn_out, new_kv = L.apply_attention(
        bp["attn"], h, cfg, positions=positions, mask=mask,
        kv_cache=kv, cache_positions=cache_positions, lengths=lengths,
        layer=layer,
    )
    if "norm_attn_post" in bp:
        attn_out = L.apply_norm(bp["norm_attn_post"], attn_out, cfg)
    x = x + attn_out

    h = L.apply_norm(bp["norm_mlp"], x, cfg)
    if cfg.is_moe:
        mlp_out = M.apply_moe(bp["moe"], h, cfg, ep)
        if cfg.moe_dense_residual_ff:
            mlp_out = mlp_out + L.apply_mlp(bp["mlp"], h, cfg)
    else:
        mlp_out = L.apply_mlp(bp["mlp"], h, cfg)
    if "norm_mlp_post" in bp:
        mlp_out = L.apply_norm(bp["norm_mlp_post"], mlp_out, cfg)
    return x + mlp_out, new_kv


def _remat(fn, cfg: ModelConfig):
    if cfg.remat_policy == "none":
        return fn
    if cfg.remat_policy == "full":
        return jax.checkpoint(fn, prevent_cse=False)
    return jax.checkpoint(
        fn,
        policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        prevent_cse=False,
    )


def _run_blocks(
    params, x, cfg: ModelConfig, *,
    positions, mask, cache=None, cache_positions=None, lengths=None,
    ep=None, remat=False,
):
    windows = layer_windows(cfg)

    def block(x, bp, window, kv, layer=None, ring=False):
        fn = functools.partial(
            _block_fn, cfg=cfg, positions=positions, mask=mask,
            cache_positions=cache_positions, lengths=lengths, ep=ep,
            ring=ring,
        )
        if remat:
            fn = _remat(fn, cfg)
        return fn(bp, x, window=window, kv=kv, layer=layer)

    if not cfg.scan_layers:
        # Unrolled (dry-run mode: exact cost_analysis; scan bodies are only
        # counted once by XLA's static cost model).
        ring_cache = cache is not None and isinstance(cache["k"], tuple)
        if ring_cache:
            ck, cv = list(cache["k"]), list(cache["v"])
            max_s = max(a.shape[1] for a in ck)
        kv = None if cache is None else (cache["k"], cache["v"])
        for i in range(cfg.num_layers):
            bp = jax.tree.map(lambda a: a[i], params["blocks"])
            if ring_cache:
                x, (ck[i], cv[i]) = block(x, bp, windows[i], (ck[i], cv[i]),
                                          ring=ck[i].shape[1] < max_s)
            elif cache is None:
                x, _ = block(x, bp, windows[i], None)
            else:
                x, kv = block(x, bp, windows[i], kv, layer=i)
        if cache is None:
            return x, None
        if ring_cache:
            return x, {"k": tuple(ck), "v": tuple(cv)}
        return x, {"k": kv[0], "v": kv[1]}

    if cache is None:
        def train_body(x, scanned):
            bp, window = scanned
            return block(x, bp, window, None)[0], None
        x, _ = lax.scan(train_body, x, (params["blocks"], windows))
        return x, None

    # the stacked cache is the scan's carry, updated in place layer by layer
    def cached_body(carry, scanned):
        x, k, v = carry
        bp, window, layer = scanned
        x, (k, v) = block(x, bp, window, (k, v), layer=layer)
        return (x, k, v), None

    (x, k, v), _ = lax.scan(
        cached_body, (x, cache["k"], cache["v"]),
        (params["blocks"], windows, jnp.arange(cfg.num_layers)))
    return x, {"k": k, "v": v}


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------


def _embed_inputs(params, tokens, cfg: ModelConfig, prefix_embeds=None):
    x = L.embed(params["embed"], tokens, cfg)
    if prefix_embeds is not None:
        vis = (prefix_embeds @ params["vision_proj"]).astype(x.dtype)
        x = jnp.concatenate([vis, x], axis=1)
    return x


def forward_train(params, tokens, cfg: ModelConfig, ep=None, prefix_embeds=None):
    """tokens (B, S) -> logits (B, S_total, V)."""
    x = _embed_inputs(params, tokens, cfg, prefix_embeds)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    x, _ = _run_blocks(
        params, x, cfg, positions=positions, mask=MaskSpec("causal"),
        ep=ep, remat=True,
    )
    x = L.apply_norm(params["final_norm"], x, cfg)
    return L.unembed(params["embed"], x, cfg)


def lm_loss(params, batch, cfg: ModelConfig, ep=None):
    """batch: {tokens (B,S), labels (B,S)} -> scalar CE."""
    logits = forward_train(params, batch["tokens"], cfg, ep=ep,
                           prefix_embeds=batch.get("prefix_embeds"))
    labels = batch["labels"]
    if logits.shape[1] != labels.shape[1]:      # vlm prefix: loss on text only
        logits = logits[:, -labels.shape[1]:]
    return L.softmax_xent(logits, labels)


def prefill(params, cache, tokens, lengths, cfg: ModelConfig, ep=None,
            prefix_embeds=None):
    """Full-prompt prefill. tokens (B, S) padded; KV written at [0, S).
    Returns (last_token_logits (B, V), cache)."""
    x = _embed_inputs(params, tokens, cfg, prefix_embeds)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    zero = jnp.zeros((b,), jnp.int32)
    x, cache = _run_blocks(
        params, x, cfg, positions=positions, mask=MaskSpec("causal"),
        cache=cache, cache_positions=zero, ep=ep,
    )
    x = L.apply_norm(params["final_norm"], x, cfg)
    idx = jnp.clip(lengths - 1, 0, s - 1)
    last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
    return L.unembed(params["embed"], last[:, None], cfg)[:, 0], cache


def prefill_chunk(params, cache, chunk, starts, cfg: ModelConfig, ep=None,
                  take=None):
    """Chunked prefill: chunk (B, Sc) continues requests whose first
    ``starts[b]`` tokens are already in the cache. ``take`` (B,) selects the
    per-request last real token (chunks may be bucket-padded); default Sc."""
    x = _embed_inputs(params, chunk, cfg)
    b, sc, _ = x.shape
    positions = starts[:, None] + jnp.arange(sc)[None]
    x, cache = _run_blocks(
        params, x, cfg, positions=positions, mask=MaskSpec("chunk"),
        cache=cache, cache_positions=starts, lengths=starts, ep=ep,
    )
    x = L.apply_norm(params["final_norm"], x, cfg)
    idx = jnp.clip((take if take is not None else sc) - 1, 0, sc - 1)
    if not hasattr(idx, "shape") or idx.ndim == 0:
        last = x[:, idx][:, None]
    else:
        last = jnp.take_along_axis(x, idx[:, None, None], axis=1)
    return L.unembed(params["embed"], last, cfg)[:, 0], cache


def decode(params, cache, tokens, lengths, cfg: ModelConfig, ep=None):
    """One decode step. tokens (B,) int32 — the freshly sampled token, to be
    written at position lengths[b]. Returns (logits (B, V), cache).
    With the stacked cache, attention reads only each row's live positions
    (``kernels/decode_attention.py``); a row with a negative length does
    not decode: its logits are to be ignored, and its write lands at the
    slot's last position, which the slot's own later steps write before
    they read it."""
    x = L.embed(params["embed"], tokens[:, None], cfg)
    b = x.shape[0]
    positions = lengths[:, None]
    x, cache = _run_blocks(
        params, x, cfg, positions=positions, mask=MaskSpec("lengths"),
        cache=cache, cache_positions=lengths, lengths=lengths, ep=ep,
    )
    x = L.apply_norm(params["final_norm"], x, cfg)
    return L.unembed(params["embed"], x, cfg)[:, 0], cache
