"""Chunked-prefill attention — Pallas TPU kernel.

Tropical's multiplexing workers run prefill in chunks piggybacked on decode
batches (§IV-B): a chunk of Sq new tokens, starting at per-request offset
``starts[b]``, attends to the KV cache prefix [0, starts[b]+i] (the chunk's
own K/V have already been written at [starts, starts+Sq)).

Flash-attention layout: grid (B, Sq/bq, Sk/bk); the KV-block dim iterates
fastest and carries the online-softmax state of every query head in VMEM
scratch. KV blocks entirely above the causal frontier (or entirely below
the sliding-window floor) are skipped with @pl.when — chunked prefill
against a long prefix is mostly *skippable* work, which is where the
kernel beats a dense mask.

Each step loads all heads of its rows: q is viewed as (B, Sq, Hq*D) and
the caches as (B, Smax, Hkv*D) (free row-major reshapes), so every
block's last two dims are (rows, full lane extent) and Mosaic tiles them
for any head count. A KV head's K/V and each of its G = Hq/Hkv query
heads are static (rows, D) lane slices, looped inside the kernel; the K/V
tile is fetched once per step for the whole group.

Block sizes default to (bq=128|Sq, bk=256) — MXU-aligned with D=64..256.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(
    starts_ref,           # (B,) scalar prefetch
    q_ref,                # (1, bq, Hq*D)
    k_ref,                # (1, bk, Hkv*D)
    v_ref,                # (1, bk, Hkv*D)
    o_ref,                # (1, bq, Hq*D)
    m_ref, l_ref,         # (Hq, bq, 1) f32
    acc_ref,              # (Hq, bq, D) f32
    *,
    bq: int,
    bk: int,
    hkv: int,
    n_kv_blocks: int,
    softcap,
    window,
):
    b = pl.program_id(0)
    iq = pl.program_id(1)
    jk = pl.program_id(2)
    start = starts_ref[b]
    hq, _, d = acc_ref.shape
    g = hq // hkv

    @pl.when(jk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal frontier for this q block: kpos <= start + iq*bq + (bq-1)
    hi = start + (iq + 1) * bq
    lo = 0 if window is None else start + iq * bq - window + 1
    block_lo = jk * bk
    relevant = (block_lo < hi) if window is None else (
        (block_lo < hi) & (block_lo + bk > lo))

    @pl.when(relevant)
    def _step():
        qpos = start + iq * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, 1), 0)
        kpos = block_lo + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        ok = kpos <= qpos                                       # (bq, bk)
        if window is not None:
            ok = ok & (kpos > qpos - window)
        for h in range(hkv):
            k = k_ref[0, :, h * d:(h + 1) * d].astype(jnp.float32)  # (bk, D)
            v = v_ref[0, :, h * d:(h + 1) * d].astype(jnp.float32)
            for j in range(h * g, (h + 1) * g):
                q = q_ref[0, :, j * d:(j + 1) * d].astype(jnp.float32)
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * (1.0 / math.sqrt(d))                        # (bq, bk)
                if softcap is not None:
                    s = softcap * jnp.tanh(s / softcap)
                s = jnp.where(ok, s, NEG_INF)

                m_prev = m_ref[j]
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.exp(s - m_new)
                l_ref[j] = alpha * l_ref[j] + jnp.sum(p, axis=1, keepdims=True)
                acc_ref[j] = alpha * acc_ref[j] + jax.lax.dot_general(
                    p, v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                m_ref[j] = m_new

    @pl.when(jk == n_kv_blocks - 1)
    def _finalize():
        for j in range(hq):
            l = jnp.maximum(l_ref[j], 1e-30)
            o_ref[0, :, j * d:(j + 1) * d] = (acc_ref[j] / l).astype(
                o_ref.dtype)


def chunked_prefill_attention(
    q: jax.Array,            # (B, Sq, Hq, D) — the chunk's queries (roped)
    k_cache: jax.Array,      # (B, Smax, Hkv, D) — chunk K/V already written
    v_cache: jax.Array,
    starts: jax.Array,       # (B,) int32 chunk start offsets
    *,
    softcap: float | None = None,
    window: int | None = None,
    bq: int = 128,
    bk: int = 256,
    interpret: bool = False,
) -> jax.Array:
    b, sq, hq, d = q.shape
    smax, hkv = k_cache.shape[1], k_cache.shape[2]
    assert hq % hkv == 0, (hq, hkv)
    bq = min(bq, sq)
    bk = min(bk, smax)
    assert sq % bq == 0 and smax % bk == 0, (sq, bq, smax, bk)
    n_kv_blocks = smax // bk

    def q_map(bi, iq, jk, st):
        return (bi, iq, 0)

    def kv_map(bi, iq, jk, st):
        return (bi, jk, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, sq // bq, n_kv_blocks),
        in_specs=[
            pl.BlockSpec((1, bq, hq * d), q_map),
            pl.BlockSpec((1, bk, hkv * d), kv_map),
            pl.BlockSpec((1, bk, hkv * d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, hq * d), q_map),
        scratch_shapes=[
            pltpu.VMEM((hq, bq, 1), jnp.float32),
            pltpu.VMEM((hq, bq, 1), jnp.float32),
            pltpu.VMEM((hq, bq, d), jnp.float32),
        ],
    )

    kernel = pl.pallas_call(
        functools.partial(_kernel, bq=bq, bk=bk, hkv=hkv,
                          n_kv_blocks=n_kv_blocks, softcap=softcap,
                          window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, sq, hq * d), q.dtype),
        interpret=interpret,
    )
    out = kernel(starts, q.reshape(b, sq, hq * d),
                 k_cache.reshape(b, smax, hkv * d),
                 v_cache.reshape(b, smax, hkv * d))
    return out.reshape(b, sq, hq, d)
