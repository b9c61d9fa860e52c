"""The stacked KV cache is head-major, (L, B, Hkv, S, D), and the model
step writes into it in place: prefill and decode carry it through the
layer scan, each layer writes its new rows into it and reads its own
layer back. These tests pin what that must not change: the logits, the
positions a step writes, the agreement of the scanned and unrolled paths,
and where the cache's sharding puts the KV-head axis."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_smoke
from repro.models import sharding
from repro.models import transformer as T

# float32 smoke configs: qwen2 is GQA (6 query heads over 2 KV heads),
# stablelm MHA (4 over 4)
ARCHS = ["qwen2-1.5b", "stablelm-1.6b"]
BATCH, PROMPT, STEPS, MAX_LEN = 2, 8, 4, 16


def _setup(arch):
    cfg = get_smoke(arch)
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1),
                              (BATCH, PROMPT + STEPS), 0, cfg.vocab_size)
    return cfg, params, toks


def _prefill_then_decode(cfg, params, toks):
    """Chunked prefill of the prompt, then teacher-forced decode steps,
    each step jitted as it is served: the logits at positions PROMPT-1 ..
    PROMPT+STEPS-1, and the cache."""
    prefill = jax.jit(lambda p, c, t, s: T.prefill_chunk(p, c, t, s, cfg))
    decode = jax.jit(lambda p, c, t, n: T.decode(p, c, t, n, cfg))
    cache = T.init_cache(cfg, BATCH, MAX_LEN)
    zero = jnp.zeros((BATCH,), jnp.int32)
    last, cache = prefill(params, cache, toks[:, :PROMPT], zero)
    out = [last]
    for i in range(STEPS):
        lengths = jnp.full((BATCH,), PROMPT + i, jnp.int32)
        logits, cache = decode(params, cache, toks[:, PROMPT + i], lengths)
        out.append(logits)
    return jnp.stack(out, axis=1), cache


def test_cache_is_head_major():
    cfg = get_smoke("qwen2-1.5b")
    spec = T.cache_spec(cfg, BATCH, MAX_LEN)
    want = (cfg.num_layers, BATCH, cfg.num_kv_heads, MAX_LEN, cfg.head_dim)
    assert spec["k"].shape == spec["v"].shape == want


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_and_decode_match_forward(arch):
    cfg, params, toks = _setup(arch)
    got, _ = _prefill_then_decode(cfg, params, toks)
    want = T.forward_train(params, toks, cfg)[:, PROMPT - 1:PROMPT + STEPS]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("new_tokens", [1, 3])
def test_step_writes_only_its_positions(arch, new_tokens):
    """A decode step (one new token per slot) or a prefill chunk (three)
    writes positions ``starts[b]`` onward of each slot in each layer, and
    leaves every other cache entry bit-identical."""
    cfg, params, _ = _setup(arch)
    shape = T.cache_spec(cfg, BATCH, MAX_LEN)["k"].shape
    k0, v0 = jax.random.normal(jax.random.PRNGKey(2), (2,) + shape)
    starts = jnp.asarray([5, 11], jnp.int32)
    tokens = jnp.asarray([[3, 9, 4], [7, 1, 8]], jnp.int32)[:, :new_tokens]
    cache = {"k": k0, "v": v0}
    if new_tokens == 1:
        _, cache = T.decode(params, cache, tokens[:, 0], starts, cfg)
    else:
        _, cache = T.prefill_chunk(params, cache, tokens, starts, cfg)
    written = np.zeros(shape, bool)
    for b, n in enumerate(np.asarray(starts)):
        written[:, b, :, n:n + new_tokens] = True
    for old, new in ((k0, cache["k"]), (v0, cache["v"])):
        old, new = np.asarray(old), np.asarray(new)
        np.testing.assert_array_equal(new[~written], old[~written])
        assert np.all(new[written] != old[written])


@pytest.mark.parametrize("arch", ARCHS)
def test_scanned_and_unrolled_paths_agree(arch):
    cfg, params, toks = _setup(arch)
    logits, cache = _prefill_then_decode(cfg, params, toks)
    u_logits, u_cache = _prefill_then_decode(
        dataclasses.replace(cfg, scan_layers=False), params, toks)
    # XLA fuses a scan body and an unrolled layer differently: float32
    # rounding (~20 ulp after four layers) is all that may differ
    np.testing.assert_allclose(np.asarray(u_logits), np.asarray(logits),
                               rtol=0, atol=1e-5)
    for name in ("k", "v"):
        np.testing.assert_allclose(np.asarray(u_cache[name]),
                                   np.asarray(cache[name]), rtol=0, atol=1e-5)


@pytest.mark.parametrize("arch,want", [
    # 4 KV heads over the 4-way 'model' axis: the heads (axis 2) are split
    ("stablelm-1.6b", P(None, "data", "model", None, None)),
    # 2 KV heads do not divide it: the sequence (axis 3) is split instead
    ("qwen2-1.5b", P(None, "data", None, "model", None)),
])
def test_cache_specs_put_kv_heads_on_axis_2(arch, want):
    cfg = get_smoke(arch)
    rules = sharding.make_rules(AbstractMesh((2, 4), ("data", "model")))
    specs = sharding.cache_specs(cfg, rules, BATCH,
                                 T.cache_spec(cfg, BATCH, MAX_LEN))
    assert specs["k"] == specs["v"] == want


def test_cache_specs_keep_zamba2_sequence_major():
    """zamba2's shared-attention cache stays (ninv, B, S, Hkv, D)."""
    from repro.models import zamba2
    cfg = get_smoke("zamba2-2.7b")
    assert cfg.num_kv_heads % 4 == 0
    rules = sharding.make_rules(AbstractMesh((2, 4), ("data", "model")))
    tree = zamba2.state_spec(cfg, BATCH, MAX_LEN)
    specs = sharding.cache_specs(cfg, rules, BATCH, tree)
    assert specs["k"] == specs["v"] == P(None, "data", None, "model", None)
