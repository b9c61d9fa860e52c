"""A run with the timed path broken underneath comes out not correct.

Each test drives the whole run but the look for a chip (``run_cell`` on
the CPU, smoke config) and breaks the served path where it produces its
output: a token altered, the decode step returning its cache unchanged,
half of the decode batch left out, the KV exchange between workers left
out. The sound run is correct."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import run

HERE = Path(__file__).resolve().parent
CFG = json.loads((HERE / "smoke" / "qwen2-smoke.json").read_text())
ONE = json.loads((HERE / "smoke" / "short.w1.json").read_text())
# two workers that always migrate (prefill on one, decode on the other)
TWO = dict(ONE, workers=2, policy="distserve")
SEED = 2**31 + 11


def _wrap_decode(execs, make):
    k = execs.kernels
    k._decode = make(k.decode_fn, k.api)


def token_altered(execs):
    vocab = execs.kernels.api.cfg.vocab_size

    def make(step, _api):
        def f(p, c, t, l):
            toks, c = step(p, c, t, l)
            return (toks + 1) % vocab, c
        return f
    _wrap_decode(execs, make)


def cache_unchanged(execs):
    def make(_step, api):
        @jax.jit
        def f(p, c, t, l):
            logits, _ = api.decode(p, c, t, l)
            return jnp.argmax(logits, -1).astype(jnp.int32), c
        return f
    _wrap_decode(execs, make)


def half_batch(execs):
    def make(step, _api):
        def f(p, c, t, l):
            toks, c = step(p, c, t, l)
            half = toks.shape[0] // 2
            return toks.at[half:].set(0), c
        return f
    _wrap_decode(execs, make)


def exchange_left_out(execs):
    """Migration bookkeeping as the program does it, minus the KV row."""
    def migrate(req, src, dst):
        se, de = execs.execs[src], execs.execs[dst]
        de.prompts[req.rid] = se.prompts[req.rid]
        de.generated[req.rid] = list(se.generated[req.rid])
        slot = de._slot(req.rid)
        execs.migrations.append((req.rid, src, dst))
        de.lengths[slot] = se.lengths[se.slot_of[req.rid]]
        se.release(req.rid)
    execs.migrate = migrate


def _run(traffic, faults=None):
    return run.run_cell(CFG, traffic, SEED, 3.0, False, jax.devices()[:1],
                        0.0, faults=faults)


@pytest.mark.parametrize("traffic", [ONE, TWO], ids=["one", "two"])
def test_sound_run_is_correct(traffic):
    rec = _run(traffic)
    assert rec.correct, rec.checks
    assert rec.checks["widest_logit_gap"]["value"] <= CFG["check"]["gap_limit"]
    if traffic is TWO:
        assert rec.migrated_rids


@pytest.mark.parametrize("fault", [token_altered, cache_unchanged,
                                   half_batch])
def test_broken_decode_is_not_correct(fault):
    rec = _run(ONE, fault)
    assert not rec.correct
    assert rec.checks["widest_logit_gap"]["value"] \
        > CFG["check"]["gap_limit"]


def test_exchange_left_out_is_not_correct():
    rec = _run(TWO, exchange_left_out)
    assert rec.migrated_rids
    assert not rec.correct
