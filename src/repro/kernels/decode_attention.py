"""Decode attention over the stacked slot cache — Pallas TPU kernel.

One new query token per slot attends over that slot's live positions of
one layer of the model's stacked, head-major KV cache, read in place:
no slice, transpose or copy of the cache is made for it, and positions
outside a row's live blocks are never fetched. A decode step thus reads
each request's context, not every slot at ``max_len``.

Layout
------
  q:        (B, Hq, D)              — rope already applied
  k_cache:  (L, B, Hkv, S, D)       — the whole stacked cache, in HBM
  v_cache:  (L, B, Hkv, S, D)
  scalar prefetch: ``layer``, the layer's ``window`` (``NO_WINDOW`` is
  global) and ``lengths`` (B,)

Row ``b`` attends over positions ``[max(0, lengths[b] - window + 1),
lengths[b]]`` inclusive: ``MaskSpec("lengths", window)``'s set, which
holds the token written this step. A row with a negative length does not
decode this step (a free slot, or one still in prefill): its output is
zeros.

K and V are fetched in blocks of (Hkv, block, D) — every KV head of
``block`` positions of one row — and the G = Hq/Hkv query rows of a KV
head are handled together, with an online softmax in float32. A float8
cache is cast up to the query's dtype inside the kernel, as the XLA path
casts it. Two forms, by head dim:

* D a multiple of 128 (the lane width): grid (B,). Each step loops over
  its row's live blocks (``live_blocks``), double-buffering K and V with
  ``pltpu.make_async_copy`` from the cache left in HBM. A row that does
  not decode costs one grid step and fetches nothing.
* Otherwise (D = 64, say): the TPU compiler will not slice such a
  narrow minor axis by hand, so the blocks come through BlockSpecs over
  a (B, S / block) grid whose steps past a row's live blocks repeat its
  last block index, which issues no new fetch. A row that does not
  decode still fetches one block.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 512                  # positions per fetched block, at most
BLOCK_BYTES = 1 << 19        # bytes of one fetched K (or V) block, at most
LANES = 128
NEG_INF = -1e30
NO_WINDOW = jnp.iinfo(jnp.int32).max // 2   # a window that never masks


def block_size(seq_len: int, position_bytes: int) -> int:
    """Positions per fetched block for a cache of ``seq_len`` positions
    whose K holds ``position_bytes`` per position (all KV heads): the
    largest power of two that divides ``seq_len``, up to ``BLOCK``
    positions and ``BLOCK_BYTES`` (so that the double-buffered K and V
    fit the TPU's fast memory at any head count)."""
    cap = BLOCK
    while cap > 32 and cap * position_bytes > BLOCK_BYTES:
        cap //= 2
    return math.gcd(seq_len, cap)


def live_blocks(lengths, window, block: int):
    """(first block, number of blocks) the kernel reads for rows of these
    ``lengths`` under ``window``: the blocks that hold positions
    ``[max(0, lengths - window + 1), lengths]``, none for a negative
    length. Plain arithmetic, so it serves numpy arrays on the host and
    traced scalars in the kernel alike."""
    lo = lengths - window + 1
    lo = lo * (lo > 0)
    first = lo // block
    return first, (lengths // block - first + 1) * (lengths >= 0)


def _init(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _update(q_ref, k_ref, v_ref, at, j, length, window, m_ref, l_ref,
            acc_ref, *, block, softcap):
    """Fold block ``j`` of one row into its running softmax: K and V of
    head h are ``k_ref[at + (h,)]``, ``v_ref[at + (h,)]`` (block, D);
    positions outside the row's live range are masked."""
    hkv, _, d = acc_ref.shape
    dtype = q_ref.dtype
    pos = j * block + lax.broadcasted_iota(jnp.int32, (1, block), 1)
    live = (pos > length - window) & (pos <= length)
    for h in range(hkv):
        s = lax.dot_general(
            q_ref[0, h], k_ref[at + (h,)].astype(dtype),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * (1.0 / math.sqrt(d))
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(live, s, NEG_INF)                           # (G, bs)
        m_prev = m_ref[h]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[h] = alpha * acc_ref[h] + lax.dot_general(
            p.astype(dtype), v_ref[at + (h,)].astype(dtype),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[h] = m_new


def _finish(o_ref, l_ref, acc_ref, count):
    out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = jnp.where(count > 0, out, 0.0).astype(o_ref.dtype)


def _loop_kernel(layer_ref, window_ref, lengths_ref, q_ref, k_hbm, v_hbm,
                 o_ref, k_buf, v_buf, sems, m_ref, l_ref, acc_ref, *,
                 block, softcap):
    """Grid (B,): q/o blocks (1, Hkv, G, D); ``k_hbm``/``v_hbm`` the whole
    cache in HBM; ``k_buf``/``v_buf`` (2, Hkv, block, D) with DMA
    semaphores (K/V, buffer)."""
    b = pl.program_id(0)
    layer, window, length = layer_ref[0], window_ref[0], lengths_ref[b]
    first, count = live_blocks(length, window, block)

    def copies(j, slot):
        start = pl.multiple_of(j * block, block)
        return [pltpu.make_async_copy(
                    src.at[layer, b, :, pl.ds(start, block)], buf.at[slot],
                    sems.at[n, slot])
                for n, (src, buf) in enumerate(((k_hbm, k_buf),
                                                (v_hbm, v_buf)))]

    _init(m_ref, l_ref, acc_ref)

    @pl.when(count > 0)
    def _attend():
        for c in copies(first, 0):
            c.start()

        def body(i, carry):
            j, slot = first + i, i % 2

            @pl.when(i + 1 < count)
            def _prefetch():
                for c in copies(j + 1, 1 - slot):
                    c.start()

            for c in copies(j, slot):
                c.wait()
            _update(q_ref, k_buf, v_buf, (slot,), j, length, window,
                    m_ref, l_ref, acc_ref, block=block, softcap=softcap)
            return carry

        lax.fori_loop(0, count, body, 0)

    _finish(o_ref, l_ref, acc_ref, count)


def _grid_kernel(layer_ref, window_ref, lengths_ref, q_ref, k_ref, v_ref,
                 o_ref, m_ref, l_ref, acc_ref, *, block, softcap):
    """Grid (B, S / block): step (b, i) holds block ``first + i`` of row
    b in ``k_ref``/``v_ref`` (1, 1, Hkv, block, D) while i < count."""
    b, i = pl.program_id(0), pl.program_id(1)
    window, length = window_ref[0], lengths_ref[b]
    first, count = live_blocks(length, window, block)

    @pl.when(i == 0)
    def _start():
        _init(m_ref, l_ref, acc_ref)

    @pl.when(i < count)
    def _attend():
        _update(q_ref, k_ref, v_ref, (0, 0), first + i, length, window,
                m_ref, l_ref, acc_ref, block=block, softcap=softcap)

    @pl.when(i == pl.num_programs(1) - 1)
    def _end():
        _finish(o_ref, l_ref, acc_ref, count)


def decode_attention(
    q: jax.Array,               # (B, Hq, D)
    k_cache: jax.Array,         # (L, B, Hkv, S, D)
    v_cache: jax.Array,
    layer,                      # int or i32 scalar
    window,                     # int or i32 scalar; NO_WINDOW = global
    lengths: jax.Array,         # (B,) i32: the position written this step;
                                # negative: the row does not decode
    *,
    softcap: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Attention of each row's query over its live positions of layer
    ``layer``; returns (B, Hq, D) in q's dtype."""
    b, hq, d = q.shape
    _, _, hkv, seq, _ = k_cache.shape
    g = hq // hkv
    assert g * hkv == hq, (hq, hkv)
    block = block_size(seq, hkv * d * k_cache.dtype.itemsize)
    scratch = [pltpu.VMEM((hkv, g, 1), jnp.float32),
               pltpu.VMEM((hkv, g, 1), jnp.float32),
               pltpu.VMEM((hkv, g, d), jnp.float32)]
    if d % LANES == 0:
        kernel, grid = _loop_kernel, (b,)
        kv_specs = [pl.BlockSpec(memory_space=pl.ANY)] * 2
        scratch = [pltpu.VMEM((2, hkv, block, d), k_cache.dtype),
                   pltpu.VMEM((2, hkv, block, d), v_cache.dtype),
                   pltpu.SemaphoreType.DMA((2, 2))] + scratch
    else:
        def kv_map(i, j, layer_ref, window_ref, lengths_ref):
            first, count = live_blocks(lengths_ref[i], window_ref[0], block)
            return (layer_ref[0], i, 0,
                    first + jnp.clip(j, 0, jnp.maximum(count - 1, 0)), 0)

        kernel, grid = _grid_kernel, (b, seq // block)
        kv_specs = [pl.BlockSpec((1, 1, hkv, block, d), kv_map)] * 2
    row = pl.BlockSpec((1, hkv, g, d),
                       lambda i, *_: (i, 0, 0, 0))
    call = pl.pallas_call(
        functools.partial(kernel, block=block, softcap=softcap),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=grid, in_specs=[row] + kv_specs,
            out_specs=row, scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid)),
        interpret=interpret,
        name="decode_attention",
    )
    scalar = lambda x: jnp.asarray(x, jnp.int32).reshape(1)  # noqa: E731
    out = call(scalar(layer), scalar(window), lengths.astype(jnp.int32),
               q.reshape(b, hkv, g, d), k_cache, v_cache)
    return out.reshape(b, hq, d)
