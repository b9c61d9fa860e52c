"""Paged decode attention — Pallas TPU kernel.

The serving hot-spot: one new query token per request attends over a
block-table-indirected paged KV cache (vLLM-style PagedAttention, adapted
to TPU: flash-decoding accumulation across sequentially-iterated grid
steps instead of CUDA split-K + shared-memory reduction).

Layout
------
  q:            (B, Hq, D)
  k_pages:      (n_pages, page_size, Hkv, D)   — global page pool
  v_pages:      (n_pages, page_size, Hkv, D)
  block_tables: (B, max_pages) int32           — per-request page ids
  lengths:      (B,) int32                     — tokens in cache (incl. new)

Grid: (B, max_pages) — the page dim iterates fastest; the kernel carries
a running (m, l, acc) online-softmax state per KV head in VMEM scratch
across page steps and writes the output at the last page. Pages beyond a
request's length are skipped via @pl.when (their page id is clamped;
contribution masked). The page id feeds the k/v BlockSpec index_map via
scalar prefetch (pltpu.PrefetchScalarGridSpec) — the TPU-native form of
the paged indirection.

Each step DMAs one whole page, all KV heads at once: the pool is viewed
as (n_pages, page_size, Hkv*D) (a free row-major reshape), so the block's
last two dims are (page_size, Hkv*D) — a multiple of 8 sublanes and the
full lane extent, which Mosaic tiles for any Hkv. A block of one KV head,
(1, page_size, 1, D), puts Hkv on the sublane axis and is refused unless
Hkv == 1. The heads are then static lane slices of that tile, each
(page_size, D), looped inside the kernel; the G = Hq/Hkv query rows of a
KV head are a leading-axis index of the (Hkv, G, D) query block, so G
need not be a multiple of 8 (qwen2: G = 6).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_PAGE = 64
NEG_INF = -1e30


def _kernel(
    # scalar prefetch
    block_tables_ref,   # (B, max_pages)
    lengths_ref,        # (B,)
    # inputs
    q_ref,              # (1, Hkv, G, D)
    k_ref,              # (1, page_size, Hkv*D)
    v_ref,              # (1, page_size, Hkv*D)
    # outputs
    o_ref,              # (1, Hkv, G, D)
    # scratch
    m_ref,              # (Hkv, G, 1) f32
    l_ref,              # (Hkv, G, 1) f32
    acc_ref,            # (Hkv, G, D) f32
    *,
    page_size: int,
    max_pages: int,
    softcap,
):
    b = pl.program_id(0)
    i = pl.program_id(1)
    length = lengths_ref[b]
    hkv, _, d = acc_ref.shape

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    n_valid_pages = (length + page_size - 1) // page_size

    @pl.when(i < n_valid_pages)
    def _step():
        token_pos = i * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        for h in range(hkv):
            q = q_ref[0, h].astype(jnp.float32)                   # (G, D)
            k = k_ref[0, :, h * d:(h + 1) * d].astype(jnp.float32)  # (ps, D)
            v = v_ref[0, :, h * d:(h + 1) * d].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * (1.0 / math.sqrt(d))                              # (G, ps)
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            s = jnp.where(token_pos < length, s, NEG_INF)

            m_prev = m_ref[h]                                     # (G, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)                                # (G, ps)
            l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[h] = alpha * acc_ref[h] + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_ref[h] = m_new

    @pl.when(i == max_pages - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_attention(
    q: jax.Array,               # (B, Hq, D)
    k_pages: jax.Array,         # (n_pages, page_size, Hkv, D)
    v_pages: jax.Array,
    block_tables: jax.Array,    # (B, max_pages) int32
    lengths: jax.Array,         # (B,) int32
    *,
    softcap: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    b, hq, d = q.shape
    n_pages, page_size, hkv, _ = k_pages.shape
    max_pages = block_tables.shape[1]
    g = hq // hkv
    assert g * hkv == hq, (hq, hkv)

    def q_map(bi, i, bt, ln):
        return (bi, 0, 0, 0)

    def kv_map(bi, i, bt, ln):
        # clamp invalid/out-of-range pages to 0; contribution is masked
        page = bt[bi, i]
        page = jnp.where(page < 0, 0, page)
        return (page, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, max_pages),
        in_specs=[
            pl.BlockSpec((1, hkv, g, d), q_map),
            pl.BlockSpec((1, page_size, hkv * d), kv_map),
            pl.BlockSpec((1, page_size, hkv * d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, hkv, g, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.VMEM((hkv, g, d), jnp.float32),
        ],
    )

    kernel = pl.pallas_call(
        functools.partial(_kernel, page_size=page_size, max_pages=max_pages,
                          softcap=softcap),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        interpret=interpret,
    )
    qg = q.reshape(b, hkv, g, d)   # group-major: head h's rows are qg[:, h]
    out = kernel(block_tables, lengths, qg,
                 k_pages.reshape(n_pages, page_size, hkv * d),
                 v_pages.reshape(n_pages, page_size, hkv * d))
    return out.reshape(b, hq, d)
