"""The control, the reference computed in float8, fails the limit.

At the smoke size on the CPU: the served tokens of a sound run read within
the limit; the control's first tokens, read against the float32
reference, read far above it. (On the chip the same comparison at each
cell's own size sets the limits: ``tools/control.py``.)"""
import json
from pathlib import Path

import jax
import jax.numpy as jnp

from benchmarks.chip import check, run
from benchmarks.chip.traffic import plan

HERE = Path(__file__).resolve().parent
CFG = json.loads((HERE / "smoke" / "qwen2-smoke.json").read_text())
TRAFFIC = json.loads((HERE / "smoke" / "short.w1.json").read_text())


def test_control_fails_where_the_program_passes():
    seed = 2**31 + 3
    s = run.Session(CFG, TRAFFIC, seed, jax.devices()[:1])
    rec = s.serve(plan(TRAFFIC, 512, seed, 3.0), 3.0, 20.0, 0.5)
    s.close()
    c = CFG["check"]
    picked = check.sample(rec.finished, seed, c["sample_tokens"],
                          c["max_requests"])
    ref = check.Reference(CFG, seed, jnp.float32)
    program_gap, _ = check.widest_gap(ref, picked)
    control = check.control_gap(ref, picked)
    assert program_gap <= c["gap_limit"] < control
