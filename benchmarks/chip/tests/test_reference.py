"""The plain reference against the program's model at a small size.

The reference imports nothing of the program; this test may. It shows
that the program serves the benchmark's weights for a seed, and that the
reference's float32 forward pass gives the program's float32 logits."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import program
from benchmarks.chip.reference import dense_lm

HERE = Path(__file__).resolve().parent
QWEN = json.loads((HERE / "smoke" / "qwen2-smoke.json").read_text())
INTERN = dict(QWEN, name="internlm2-smoke", model_type="internlm2",
              tie_word_embeddings=False, rms_norm_eps=1e-5,
              rope_theta=1000000)
CFGS = {"qwen2": QWEN, "internlm2": INTERN}


def _program_api(cfg):
    program.importable()
    from repro.models import api as model_api
    return model_api.build(program.model_config(cfg, jnp.float32))


@pytest.mark.parametrize("arch", sorted(CFGS))
def test_program_serves_the_benchmarks_weights(arch):
    cfg = CFGS[arch]
    tr = json.loads((HERE / "smoke" / "short.w1.json").read_text())
    _, execs = program.serve(cfg, tr, 2**31 + 5, jax.devices()[:1],
                             jnp.float32)
    want = jax.jit(dense_lm.init_params, static_argnums=(1, 2))(
        jax.random.PRNGKey(2**31 + 5), dense_lm.spec_of(cfg), jnp.float32)
    got = execs.execs[0].params
    assert jax.tree.structure(want) == jax.tree.structure(got)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and it is the layout the program's own initialiser makes
    shapes = jax.eval_shape(_program_api(cfg).init, jax.random.PRNGKey(0))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), shapes) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), want)


@pytest.mark.parametrize("arch", sorted(CFGS))
def test_reference_logits_match_program_prefill(arch):
    cfg = CFGS[arch]
    api = _program_api(cfg)
    spec = dense_lm.spec_of(cfg)
    params = dense_lm.init_params(jax.random.PRNGKey(7), spec, jnp.float32)
    n = 300
    toks = jax.random.randint(jax.random.PRNGKey(8), (1, n), 0, spec.vocab)
    want, _ = api.prefill(params, api.init_cache(1, 512), toks,
                          jnp.asarray([n], jnp.int32))
    padded = jnp.zeros((512,), jnp.int32).at[:n].set(toks[0])
    target = jnp.zeros((1, 512), jnp.int32).at[0, n - 1].set(
        jnp.argmax(want[0]))
    best, picked, arg = dense_lm.gaps(params, padded, target, spec)
    np.testing.assert_allclose(float(best[n - 1]), float(want[0].max()),
                               rtol=1e-4, atol=1e-4)
    assert int(arg[n - 1]) == int(jnp.argmax(want[0]))
    assert float(best[n - 1] - picked[0, n - 1]) == pytest.approx(0, abs=1e-6)


def test_padding_after_the_sequence_changes_nothing():
    spec = dense_lm.spec_of(QWEN)
    params = dense_lm.init_params(jax.random.PRNGKey(1), spec, jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(2), (1024,), 0, spec.vocab)
    a = dense_lm.hidden(params, toks, spec)
    b = dense_lm.hidden(params, toks.at[600:].set(0), spec)
    np.testing.assert_allclose(np.asarray(a[:600]), np.asarray(b[:600]),
                               rtol=1e-5, atol=1e-5)


def test_fp8_control_departs_from_float32():
    spec = dense_lm.spec_of(QWEN)
    params = dense_lm.init_params(jax.random.PRNGKey(1), spec, jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(2), (512,), 0, spec.vocab)
    hi = dense_lm.hidden(params, toks, spec)
    lo = dense_lm.hidden(params, toks, spec, low=True)
    rel = float(jnp.linalg.norm(hi - lo) / jnp.linalg.norm(hi))
    assert 1e-3 < rel < 0.5
