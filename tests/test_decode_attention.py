"""The decode-attention kernel over the stacked slot cache
(``kernels/decode_attention.py``), in interpret mode, against the XLA
attention it replaces (``layers._attn_block`` under a "lengths" mask);
the model's decode step through it against a forward pass; and the
executor's count of the KV bytes it fetches; and the decode step under
a mesh that shards the cache, which keeps XLA's attention."""
import dataclasses
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_smoke
from repro.core.request import Request, SLOSpec
from repro.kernels import decode_attention as DA
from repro.models import layers as Lm
from repro.models import transformer as T
from repro.serving.engine import IterationPlan
from repro.serving.executor import ClusterRealExecutors

L, S, LAYER = 3, 1024, 1
# (hq, hkv, d): GQA with G = 6 and MHA, every hkv in {1, 2, 4} at both
# head dims
HEADS = [(6, 1, 64), (1, 1, 128), (12, 2, 128), (2, 2, 64), (24, 4, 64),
         (4, 4, 128)]
TOL = {jnp.float32: 1e-5, jnp.bfloat16: 2e-2}


def _poisoned(cache, lengths, window, block):
    """``cache`` with NaN wherever the kernel must not read: every other
    layer, and every block of the attended layer outside a row's live
    blocks (all of a row that does not decode)."""
    out = np.full(cache.shape, np.nan, np.float32)
    first, count = DA.live_blocks(np.asarray(lengths), window, block)
    for b, (f, n) in enumerate(zip(first, count)):
        live = slice(f * block, (f + n) * block)
        out[LAYER, b, :, live] = np.asarray(cache[LAYER, b, :, live],
                                            np.float32)
    return jnp.asarray(out, cache.dtype)


def _check(hq, hkv, d, dtype, cache_dtype=None, window=DA.NO_WINDOW,
           softcap=None):
    cache_dtype = cache_dtype or dtype
    bs = DA.block_size(S, hkv * d * jnp.dtype(cache_dtype).itemsize)
    assert 1 < bs < S
    # one batch: a row that does not decode, 0, 1, around a block edge,
    # the last position
    lengths = [-1, 0, 1, bs - 1, bs, bs + 1, S - 1]
    keys = jax.random.split(jax.random.PRNGKey(hq * 131 + d), 3)
    shape = (L, len(lengths), hkv, S, d)
    k, v = (jax.random.normal(kk, shape, jnp.float32).astype(cache_dtype)
            for kk in keys[:2])
    q = jax.random.normal(keys[2], (len(lengths), hq, d), dtype)
    got = DA.decode_attention(
        q, _poisoned(k, lengths, window, bs),
        _poisoned(v, lengths, window, bs), LAYER, window,
        jnp.asarray(lengths, jnp.int32), softcap=softcap, interpret=True)
    lengths = jnp.asarray(lengths, jnp.int32)
    kl, vl = (Lm._layer_view(c, LAYER).astype(dtype) for c in (k, v))
    want = Lm._attn_block(q[:, None], kl, vl,
                          Lm.MaskSpec("lengths", window=window), 0, softcap,
                          lengths)[:, 0]
    assert got.shape == q.shape and got.dtype == q.dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_array_equal(got[0], 0.0)     # did not decode
    np.testing.assert_allclose(got[1:], want[1:], rtol=0, atol=TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("hq,hkv,d", HEADS)
def test_kernel_matches_xla_attention(hq, hkv, d, dtype):
    _check(hq, hkv, d, dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("variant", [
    {"window": 300},                 # rows past it skip their first block
    {"softcap": 5.0},
    {"window": 40, "softcap": 5.0},
])
def test_kernel_adapts_to_window_and_softcap(variant, dtype):
    _check(12, 2, 128, dtype, **variant)


@pytest.mark.parametrize("hq,hkv,d", [(12, 2, 128), (4, 4, 64)])
def test_kernel_reads_a_float8_cache(hq, hkv, d):
    _check(hq, hkv, d, jnp.bfloat16, cache_dtype=jnp.float8_e4m3fn)


@pytest.mark.parametrize("lengths,window,first,count", [
    ([-1, 0, 1, 511, 512, 513, 1023], DA.NO_WINDOW,
     [0, 0, 0, 0, 0, 0, 0], [0, 1, 1, 1, 2, 2, 2]),
    ([-1, 0, 600, 1023, 1023], 300, [0, 0, 0, 1, 1], [0, 1, 2, 1, 1]),
])
def test_live_blocks_rule(lengths, window, first, count):
    f, n = DA.live_blocks(np.asarray(lengths), window, 512)
    assert list(f * (n > 0)) == first and list(n) == count


# ------------------------------------------------- the model's decode step

# smoke widths take the kernel's grid form; head_dim 128 its DMA loop
ARCHS = [("qwen2-1.5b", None), ("qwen2-1.5b", 128), ("stablelm-1.6b", None),
         ("internlm-20b", None), ("gemma2-2b", None), ("gemma2-2b", 128),
         ("kimi-k2-1t", None)]
BATCH, PROMPT, STEPS, MAX_LEN = 3, 8, 4, 32


@pytest.mark.parametrize("arch,head_dim", ARCHS)
def test_decode_through_the_kernel_matches_forward(arch, head_dim):
    """Prefill, then teacher-forced decode steps in which row 1 sits out
    the first two (length -1) and then decodes: every row's logits at
    the steps it decodes match a forward pass over the same tokens."""
    cfg = get_smoke(arch)
    if head_dim is not None:
        cfg = dataclasses.replace(cfg, head_dim=head_dim)
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1),
                              (BATCH, PROMPT + STEPS), 0, cfg.vocab_size)
    decode = jax.jit(lambda p, c, t, n: T.decode(p, c, t, n, cfg))
    cache = T.init_cache(cfg, BATCH, MAX_LEN)
    _, cache = T.prefill(params, cache, toks[:, :PROMPT],
                         jnp.full((BATCH,), PROMPT, jnp.int32), cfg)
    pos = np.full(BATCH, PROMPT)            # next position of each row
    got = {b: [] for b in range(BATCH)}
    for step in range(STEPS):
        active = [b for b in range(BATCH) if b != 1 or step >= 2]
        lengths = np.full(BATCH, -1, np.int32)
        lengths[active] = pos[active]
        tokens = toks[jnp.arange(BATCH), jnp.asarray(np.minimum(
            pos, PROMPT + STEPS - 1))]
        logits, cache = decode(params, cache, tokens, jnp.asarray(lengths))
        for b in active:
            got[b].append((int(pos[b]), np.asarray(logits[b])))
        pos[active] += 1
    want = np.asarray(T.forward_train(params, toks, cfg))
    for b, steps in got.items():
        assert steps
        for p, row in steps:
            np.testing.assert_allclose(row, want[b, p], atol=1e-4)


# ----------------------------------------------- the executor's byte count

def _plan(prefill=(), decode=()):
    pp, dr = list(prefill), list(decode)
    return IterationPlan(
        decode_reqs=dr, prefill_parts=pp, n_decode=len(dr),
        sum_ctx=float(sum(r.prompt_len for r in dr)),
        prefill_tokens=int(sum(t for _, t in pp)),
        prefill_ctx_offset=0.0, exclusive_prefill=not dr)


def test_executor_records_the_kv_bytes_decode_attention_fetches():
    """gemma2's layers alternate an 8-token window with global attention:
    per decoding row and layer, the blocks of 512 positions that hold
    ``[max(0, n - window + 1), n]``; a row that does not decode reads
    none."""
    cfg = get_smoke("gemma2-2b")
    execs = ClusterRealExecutors(cfg, 1, max_slots=4, max_len=1024,
                                 warmup=False)
    ex = execs.execs[0]
    slo = SLOSpec(ttft=30.0, tpot=5.0)
    a, b, c = (Request(rid=i, arrival_time=0.0, prompt_len=n,
                       output_len=4, slo=slo) for i, n in
               enumerate((520, 100, 30)))
    obs.RECORDER.reset()
    try:
        plans = [_plan(prefill=[(a, 520), (b, 100), (c, 30)]),
                 _plan(decode=[a, b]), _plan(decode=[a, c])]
        for plan in plans:
            ex.run_plan(plan)
            obs.RECORDER.iteration(plan, 0, 0.0, 1.0, 1.0)
            for r, n in plan.prefill_parts:
                r.prefilled_tokens += n
        recs = list(obs.RECORDER.iterations)
    finally:
        obs.RECORDER.reset()

    k = ex.cache["k"]
    n_layers, _, hkv, _, d = k.shape
    block_bytes = 2 * hkv * 512 * d * k.dtype.itemsize
    windows = T.layer_windows_py(cfg)

    def blocks(*ns):
        return sum(n // 512 - max(0, n - w + 1) // 512 + 1
                   for w in windows for n in ns)

    assert recs[0].kv_read_bytes is None               # prefill only
    # a at 520: 2 blocks global, 1 windowed; b, c: 1 block each
    assert blocks(520, 100) == 10 and blocks(521, 30) == 10
    assert [r.kv_read_bytes for r in recs[1:]] == [
        blocks(520, 100) * block_bytes, blocks(521, 30) * block_bytes]
    assert all(r.kv_cache_bytes == 2 * k.size * k.dtype.itemsize
               for r in recs[1:])
    assert n_layers == len(windows)


# ------------------------------------------------ the decode step, sharded

SHARDED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax, jax.numpy as jnp
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    from repro.configs import get_smoke
    from repro.models import api as model_api
    from repro.models import sharding

    PROMPT, MAX_LEN = 8, 32
    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    out = []
    # qwen2: batch over "data", KV heads over "model"; one row: the
    # sequence takes "data"; gemma2: windows and softcap
    for arch, batch in (("qwen2-1.5b", 4), ("qwen2-1.5b", 1),
                        ("gemma2-2b", 4)):
        api = model_api.build(get_smoke(arch))
        params = api.init(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1),
                                  (batch, PROMPT + 1), 0, 64)
        _, cache = api.prefill(params, api.init_cache(batch, MAX_LEN),
                               toks[:, :PROMPT],
                               jnp.full((batch,), PROMPT, jnp.int32))
        tokens = toks[:, PROMPT]
        lengths = jnp.full((batch,), PROMPT, jnp.int32)
        step = lambda p, c, t, n: api.decode(p, c, t, n)
        one = jax.jit(step)
        want, _ = one(params, cache, tokens, lengths)
        row = {"arch": arch, "batch": batch, "kernel_on_one_device":
               "pallas_call" in str(one.trace(params, cache, tokens,
                                              lengths).jaxpr)}
        with jax.set_mesh(mesh):
            rules = sharding.make_rules(mesh)
            b = sharding.batch_spec(rules, batch)
            specs = (sharding.param_specs(api.cfg, params, rules),
                     sharding.cache_specs(api.cfg, rules, batch, cache),
                     P(b), P(b))
            args = jax.tree.map(
                lambda a, sp: jax.device_put(a, NamedSharding(mesh, sp)),
                (params, cache, tokens, lengths), specs)
            sharded = jax.jit(step, in_shardings=specs,
                              out_shardings=(P(b, None), specs[1]),
                              donate_argnums=(1,))
            row["kernel_under_mesh"] = "pallas_call" in str(
                sharded.trace(*args).jaxpr)
            row["cache_sharded"] = any(
                len(a.sharding.device_set) > 1 and not
                a.sharding.is_fully_replicated
                for a in jax.tree.leaves(args[1]))
            got, _ = sharded.lower(*args).compile()(*args)
        row["gap"] = float(jnp.max(jnp.abs(got - want)))
        out.append(row)
    print(json.dumps(out))
""")


def test_sharded_decode_compiles_with_xla_attention():
    """Under a 2 x 2 mesh, with the cache laid out by
    ``sharding.cache_specs`` (batch, KV heads or sequence sharded), the
    decode step lowers and compiles without the kernel, which would need
    the whole cache on one device, and gives the one-device kernel
    step's logits."""
    env = dict(os.environ)
    src = str(pathlib.Path(__file__).resolve().parents[1] / "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-c", SHARDED_SCRIPT],
                         capture_output=True, text=True, timeout=300,
                         env=env)
    assert res.returncode == 0, res.stderr[-2000:]
    rows = json.loads(res.stdout.strip().splitlines()[-1])
    assert len(rows) == 3
    for row in rows:
        assert row["kernel_on_one_device"], row
        assert not row["kernel_under_mesh"], row
        assert row["cache_sharded"], row
        assert row["gap"] < 1e-4, row
