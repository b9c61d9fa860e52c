"""jit'd dispatch wrappers for the Pallas kernels.

On CPU (this container) the kernels execute in interpret mode — the kernel
body runs in Python per grid step, validating the exact TPU program; on TPU
they compile to Mosaic. ``auto_interpret()`` picks per-backend.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.chunked_prefill import chunked_prefill_attention
from repro.kernels.decode_attention import decode_attention
from repro.kernels.paged_attention import paged_attention
from repro.kernels.wkv6 import wkv6_chunked
from repro.kernels import ref


def auto_interpret() -> bool:
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("softcap",))
def paged_attention_op(q, k_pages, v_pages, block_tables, lengths,
                       softcap=None):
    return paged_attention(q, k_pages, v_pages, block_tables, lengths,
                           softcap=softcap, interpret=auto_interpret())


@functools.partial(jax.jit, static_argnames=("softcap", "window", "bq", "bk"))
def chunked_prefill_op(q, k_cache, v_cache, starts, softcap=None,
                       window=None, bq=128, bk=256):
    return chunked_prefill_attention(
        q, k_cache, v_cache, starts, softcap=softcap, window=window,
        bq=bq, bk=bk, interpret=auto_interpret())


_decode_attention = jax.jit(decode_attention,
                            static_argnames=("softcap", "interpret"))


def decode_attention_op(q, k_cache, v_cache, layer, window, lengths,
                        softcap=None):
    """The model step's decode attention over its stacked cache (read
    only). Interpret mode is a static argument of the jitted kernel, so
    a trace made for one backend is never reused for the other."""
    return _decode_attention(q, k_cache, v_cache, layer, window, lengths,
                             softcap=softcap, interpret=auto_interpret())


@functools.partial(jax.jit, static_argnames=("chunk",))
def wkv6_op(r, k, v, w, u, s0, chunk=64):
    return wkv6_chunked(r, k, v, w, u, s0, chunk=chunk,
                        interpret=auto_interpret())


paged_attention_ref = ref.paged_attention_ref
chunked_prefill_ref = ref.chunked_prefill_attention_ref
