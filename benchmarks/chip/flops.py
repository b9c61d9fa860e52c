"""Useful model work per iteration, from the configuration's shapes.

Useful means what the requests need: prompt and output tokens only, each
query attending over its live causal context (a prompt token at position
``p`` sees ``p + 1`` keys, a decode token the whole context it extends),
and one row of output logits per emitted token. Bucket padding, left-pad
recompute, padded batch rows and empty cache slots do not count.

Bytes are counted for pure-decode iterations only: every weight read
once (the output head in full; the input embedding only as the rows
looked up, which is left out) plus the live KV cache of each request.
"""
from __future__ import annotations

import json
from pathlib import Path

from benchmarks.chip.reference.dense_lm import Spec

PEAKS = json.loads((Path(__file__).with_name("peaks.json")).read_text())
BF16 = 2


def peaks(device_kind: str) -> dict:
    """The peak table's entry for a JAX ``device_kind``; unknown is an
    error, not a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}")
    return PEAKS[device_kind]


def layer_matmul_params(s: Spec) -> int:
    q = s.heads * s.head_dim
    kv = s.kv_heads * s.head_dim
    return 2 * s.d_model * q + 2 * s.d_model * kv + 3 * s.d_model * s.d_ff


def head_params(s: Spec) -> int:
    return s.vocab * s.d_model


def kv_bytes_per_token(s: Spec) -> int:
    return s.layers * 2 * s.kv_heads * s.head_dim * BF16


def prefill_flops(s: Spec, start: int, tokens: int, emits: bool) -> float:
    keys = tokens * start + tokens * (tokens + 1) // 2
    return (2.0 * s.layers * layer_matmul_params(s) * tokens
            + 4.0 * s.layers * s.heads * s.head_dim * keys
            + (2.0 * head_params(s) if emits else 0.0))


def decode_flops(s: Spec, context: int) -> float:
    return (2.0 * s.layers * layer_matmul_params(s)
            + 4.0 * s.layers * s.heads * s.head_dim * context
            + 2.0 * head_params(s))


def iteration_flops(s: Spec, prefill, decode_ctx) -> float:
    """``prefill``: (start, tokens, emits) parts; ``decode_ctx``: the
    context length of each decoding request."""
    return (sum(prefill_flops(s, st, n, last) for st, n, last in prefill)
            + sum(decode_flops(s, c) for c in decode_ctx))


def weight_bytes(s: Spec) -> int:
    return BF16 * (s.layers * layer_matmul_params(s) + head_params(s))


def decode_bytes(s: Spec, decode_ctx) -> float:
    return float(weight_bytes(s) + kv_bytes_per_token(s) * sum(decode_ctx))
