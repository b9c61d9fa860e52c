"""Pallas kernel validation: shape/dtype sweeps + hypothesis properties,
interpret mode vs pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ref
from repro.kernels.chunked_prefill import chunked_prefill_attention
from repro.kernels.paged_attention import paged_attention


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


def _mk_paged(rng, b, hq, hkv, d, ps, mp, dtype):
    n_pages = b * mp + 3
    q = jnp.asarray(rng.normal(size=(b, hq, d)), dtype)
    kp = jnp.asarray(rng.normal(size=(n_pages, ps, hkv, d)), dtype)
    vp = jnp.asarray(rng.normal(size=(n_pages, ps, hkv, d)), dtype)
    bt = jnp.asarray(
        rng.permutation(n_pages)[: b * mp].reshape(b, mp), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, mp * ps + 1, size=(b,)), jnp.int32)
    return q, kp, vp, bt, lengths


# ------------------------------------------------------------- paged decode

PAGED_SWEEP = [
    # (b, hq, hkv, d, page, max_pages, dtype)
    (1, 4, 4, 64, 16, 4, jnp.float32),      # MHA
    (3, 8, 2, 64, 16, 8, jnp.float32),      # GQA
    (2, 8, 1, 128, 32, 4, jnp.float32),     # MQA
    (2, 16, 4, 128, 64, 4, jnp.float32),    # serving-like tiles
    (3, 8, 2, 64, 16, 8, jnp.bfloat16),
    (2, 8, 8, 128, 64, 2, jnp.bfloat16),
    (3, 12, 2, 128, 16, 8, jnp.bfloat16),   # qwen2-1.5b GQA (G=6)
    (2, 12, 2, 128, 64, 4, jnp.bfloat16),
]


@pytest.mark.parametrize("b,hq,hkv,d,ps,mp,dtype", PAGED_SWEEP)
def test_paged_attention_sweep(b, hq, hkv, d, ps, mp, dtype):
    rng = np.random.default_rng(42)
    q, kp, vp, bt, lengths = _mk_paged(rng, b, hq, hkv, d, ps, mp, dtype)
    out = paged_attention(q, kp, vp, bt, lengths, interpret=True)
    want = ref.paged_attention_ref(q, kp, vp, bt, lengths)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        **_tol(dtype))


def test_paged_attention_softcap():
    rng = np.random.default_rng(7)
    q, kp, vp, bt, lengths = _mk_paged(rng, 2, 8, 4, 64, 16, 4, jnp.float32)
    out = paged_attention(q, kp, vp, bt, lengths, softcap=30.0,
                          interpret=True)
    want = ref.paged_attention_ref(q, kp, vp, bt, lengths, softcap=30.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


def test_paged_attention_single_token_cache():
    """length=1 edge: only the first token of the first page attends."""
    rng = np.random.default_rng(3)
    q, kp, vp, bt, _ = _mk_paged(rng, 2, 4, 2, 64, 16, 4, jnp.float32)
    lengths = jnp.asarray([1, 1], jnp.int32)
    out = paged_attention(q, kp, vp, bt, lengths, interpret=True)
    want = ref.paged_attention_ref(q, kp, vp, bt, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


@settings(max_examples=15, deadline=None)
@given(
    b=st.integers(1, 4),
    group=st.sampled_from([1, 2, 4]),
    hkv=st.sampled_from([1, 2, 4]),
    ps=st.sampled_from([8, 16]),
    mp=st.integers(1, 6),
    data=st.data(),
)
def test_paged_attention_property(b, group, hkv, ps, mp, data):
    """Property: kernel == oracle for random ragged lengths and shuffled
    page tables (indirection correctness)."""
    d = 64
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    q, kp, vp, bt, lengths = _mk_paged(rng, b, group * hkv, hkv, d, ps, mp,
                                       jnp.float32)
    out = paged_attention(q, kp, vp, bt, lengths, interpret=True)
    want = ref.paged_attention_ref(q, kp, vp, bt, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=3e-5,
                               atol=3e-5)


# -------------------------------------------------------- chunked prefill

CHUNK_SWEEP = [
    # (b, sq, hq, hkv, d, smax, bq, bk, window, dtype)
    (2, 64, 4, 4, 64, 256, 32, 64, None, jnp.float32),
    (1, 128, 8, 2, 64, 512, 64, 128, None, jnp.float32),
    (2, 32, 8, 1, 128, 128, 32, 64, None, jnp.float32),
    (2, 64, 4, 2, 64, 256, 32, 64, 48, jnp.float32),     # sliding window
    (2, 64, 4, 4, 64, 256, 32, 64, None, jnp.bfloat16),
    (1, 256, 16, 16, 64, 512, 128, 256, None, jnp.bfloat16),
    (1, 256, 12, 2, 128, 512, 128, 256, None, jnp.bfloat16),  # qwen2 GQA
    (2, 64, 12, 2, 128, 256, 32, 64, None, jnp.bfloat16),
]


@pytest.mark.parametrize("b,sq,hq,hkv,d,smax,bq,bk,window,dtype", CHUNK_SWEEP)
def test_chunked_prefill_sweep(b, sq, hq, hkv, d, smax, bq, bk, window, dtype):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, sq, hq, d)), dtype)
    kc = jnp.asarray(rng.normal(size=(b, smax, hkv, d)), dtype)
    vc = jnp.asarray(rng.normal(size=(b, smax, hkv, d)), dtype)
    starts = jnp.asarray(rng.integers(0, smax - sq + 1, size=(b,)), jnp.int32)
    out = chunked_prefill_attention(q, kc, vc, starts, window=window,
                                    bq=bq, bk=bk, interpret=True)
    want = ref.chunked_prefill_attention_ref(q, kc, vc, starts, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        **_tol(dtype))


def test_chunked_prefill_zero_start_is_causal_attention():
    """start=0, Smax=Sq: reduces to plain causal self-attention."""
    from repro.models.layers import MaskSpec, attention_scores
    rng = np.random.default_rng(5)
    b, sq, h, d = 2, 64, 4, 64
    q = jnp.asarray(rng.normal(size=(b, sq, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, sq, h, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, sq, h, d)), jnp.float32)
    starts = jnp.zeros((b,), jnp.int32)
    out = chunked_prefill_attention(q, k, v, starts, bq=32, bk=32,
                                    interpret=True)
    want = attention_scores(q, k, v, MaskSpec("causal"))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=3e-5,
                               atol=3e-5)


@settings(max_examples=10, deadline=None)
@given(
    b=st.integers(1, 3),
    group=st.sampled_from([1, 2]),
    hkv=st.sampled_from([1, 2]),
    nq=st.sampled_from([1, 2]),       # sq = nq * bq
    nk=st.sampled_from([2, 4]),       # smax = nk * bk
    window=st.sampled_from([None, 40]),
    data=st.data(),
)
def test_chunked_prefill_property(b, group, hkv, nq, nk, window, data):
    bq, bk, d = 32, 64, 64
    sq, smax = nq * bq, nk * bk
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    q = jnp.asarray(rng.normal(size=(b, sq, group * hkv, d)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(b, smax, hkv, d)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(b, smax, hkv, d)), jnp.float32)
    starts = jnp.asarray(rng.integers(0, smax - sq + 1, size=(b,)), jnp.int32)
    out = chunked_prefill_attention(q, kc, vc, starts, window=window,
                                    bq=bq, bk=bk, interpret=True)
    want = ref.chunked_prefill_attention_ref(q, kc, vc, starts, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=3e-5,
                               atol=3e-5)
