"""Batched real-executor fast path: token-stream bit-parity vs the scalar
reference, compile-count regression over the bucket grid, and the
SlotExhausted refusal contract (executor raise -> scheduler requeue)."""
import copy

import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core.request import Request, SLOSpec
from repro.sched.backend import SlotExhausted
from repro.serving.costmodel import WorkerSpec
from repro.serving.engine import IterationPlan
from repro.serving.executor import ClusterRealExecutors
from repro.serving.simulator import build_cluster

SLO = SLOSpec(ttft=30.0, tpot=5.0)


def _req(rid, prompt_len, output_len=8):
    return Request(rid=rid, arrival_time=0.0, prompt_len=prompt_len,
                   output_len=output_len, slo=SLO)


def _plan(prefill=(), decode=()):
    pp = [(r, int(t)) for r, t in prefill]
    dr = list(decode)
    return IterationPlan(
        decode_reqs=dr, prefill_parts=pp, n_decode=len(dr),
        sum_ctx=float(sum(r.prompt_len for r in dr)),
        prefill_tokens=int(sum(t for _, t in pp)),
        prefill_ctx_offset=0.0, exclusive_prefill=not dr)


def _drive(cfg_name, batched):
    """One fixed mixed workload on a 2-worker cluster: multi-chunk prefill
    (including a left-padded partial chunk and a same-bucket 2-row batch),
    mixed prefill+decode iterations, a mid-decode migration, and further
    decode on both workers. Returns the final per-request token streams."""
    cfg = get_smoke(cfg_name)
    execs = ClusterRealExecutors(cfg, 2, max_slots=4, max_len=64,
                                 batched=batched, warmup=False)
    e0, e1 = execs.execs[0], execs.execs[1]
    a, b, c, d = _req(0, 24), _req(1, 40), _req(2, 16), _req(3, 33)

    def step(ex, plan):
        ex.run_plan(plan)
        for r, t in plan.prefill_parts:       # the engine's bookkeeping
            r.prefilled_tokens += t

    step(e0, _plan(prefill=[(a, 16)]))
    step(e0, _plan(prefill=[(a, 8), (b, 24)]))       # same bucket, 2 rows
    step(e0, _plan(prefill=[(b, 16)], decode=[a]))   # left-pad + mixed iter
    step(e1, _plan(prefill=[(d, 33)]))               # bucket == max_len
    step(e0, _plan(decode=[a, b]))
    execs.migrate(b, 0, 1)                           # mid-decode migration
    step(e0, _plan(prefill=[(c, 16)], decode=[a]))
    step(e1, _plan(decode=[b, d]))
    step(e1, _plan(decode=[d, b]))
    step(e0, _plan(decode=[c, a]))
    streams = {0: list(e0.generated[0]), 1: list(e1.generated[1]),
               2: list(e0.generated[2]), 3: list(e1.generated[3])}
    return execs, streams


# ---------------------------------------------------------------- bit parity

def test_fast_path_token_parity_transformer():
    """batched=True must produce bit-identical token streams to the scalar
    per-request reference on a KV-cache transformer, across chunked
    prefill, fused mixed iterations and a device-to-device migration."""
    fast, s_fast = _drive("qwen2-1.5b", batched=True)
    ref, s_ref = _drive("qwen2-1.5b", batched=False)
    assert fast.execs[0].fast and fast.execs[1].fast
    assert not ref.execs[0].fast
    assert fast.kernels is not None and ref.kernels is None
    assert s_fast == s_ref
    for rid, toks in s_fast.items():
        assert len(toks) >= 2, f"rid {rid} produced too few tokens"


def test_fast_path_token_parity_stateful_fallback():
    """Stateful families (rwkv6: no positional chunk entry point) must fall
    back to the scalar reference even under batched=True — and still match
    it bit-for-bit through the same mixed workload."""
    fast, s_fast = _drive("rwkv6-7b", batched=True)
    ref, s_ref = _drive("rwkv6-7b", batched=False)
    assert not fast.execs[0].fast          # fallback engaged
    assert fast.kernels is None            # no bucketed kernels built
    assert s_fast == s_ref


# ------------------------------------------------------------- compile count

def test_compile_count_bounded_by_bucket_grid():
    """Warmup pre-traces every (bucket, rows=1) prefill entry; afterwards,
    >= 6 distinct chunk lengths must hit the jit cache (misses bounded by
    the bucket count), and decode must stay on its single trace."""
    cfg = get_smoke("qwen2-1.5b")
    execs = ClusterRealExecutors(cfg, 1, max_slots=8, max_len=128,
                                 batched=True, warmup=True)
    k = execs.kernels
    assert k is not None
    assert k.prefill_traces == len(k.buckets)
    assert k.decode_traces == 1
    e = execs.execs[0]
    takes = [3, 5, 9, 17, 33, 65]          # 6 distinct lengths, 3 buckets
    for i, t in enumerate(takes):
        r = _req(rid=100 + i, prompt_len=t)
        e.run_plan(_plan(prefill=[(r, t)]))
        r.prefilled_tokens = t
        execs.on_finish(r)                  # free the slot for the next
    assert k.prefill_traces == len(k.buckets), \
        "distinct chunk lengths must not add jit traces beyond the buckets"
    e.run_plan(_plan(decode=[]))            # empty plan: no tracing at all
    assert k.decode_traces == 1


# ------------------------------------------------------------- slot accounting

def test_slot_exhausted_is_typed_and_side_effect_free():
    cfg = get_smoke("qwen2-1.5b")
    execs = ClusterRealExecutors(cfg, 1, max_slots=2, max_len=64,
                                 warmup=False)
    e = execs.execs[0]
    for rid in (0, 1):
        e._slot(rid)
    with pytest.raises(SlotExhausted) as ei:
        e._slot(2)
    assert ei.value.wid == 0
    assert ei.value.rid == 2
    assert ei.value.max_slots == 2
    assert set(e.slot_of) == {0, 1}         # existing tenants untouched
    assert execs._owner == {0: 0, 1: 0}     # refused rid never registered


def test_run_plan_reserves_slots_before_any_compute():
    """A plan needing more slots than remain must refuse before running any
    prefill part — otherwise a re-run would double-append sampled tokens."""
    cfg = get_smoke("qwen2-1.5b")
    execs = ClusterRealExecutors(cfg, 1, max_slots=2, max_len=64,
                                 warmup=False)
    e = execs.execs[0]
    reqs = [_req(i, 16) for i in range(3)]
    with pytest.raises(SlotExhausted):
        e.run_plan(_plan(prefill=[(r, 16) for r in reqs]))
    assert all(not e.generated.get(r.rid) for r in reqs), \
        "no tokens may be sampled when the plan is refused"


def test_migrate_to_full_worker_raises_and_preserves_source():
    cfg = get_smoke("qwen2-1.5b")
    execs = ClusterRealExecutors(cfg, 2, max_slots=1, max_len=64,
                                 warmup=False)
    e0, e1 = execs.execs[0], execs.execs[1]
    a = _req(0, 16)
    e0.run_plan(_plan(prefill=[(a, 16)]))
    a.prefilled_tokens = 16
    e1._slot(99)                            # destination is full
    with pytest.raises(SlotExhausted):
        execs.migrate(a, 0, 1)
    assert a.rid in e0.slot_of              # source slot intact
    assert execs._owner[a.rid] == 0


def test_on_finish_releases_only_on_owning_executor():
    """Regression: on_finish used to call release() on EVERY executor.
    Only the owner may release — other executors' free lists must not be
    touched (a foreign release would corrupt their slot accounting)."""
    cfg = get_smoke("qwen2-1.5b")
    execs = ClusterRealExecutors(cfg, 3, max_slots=2, max_len=64,
                                 warmup=False)
    e0, e1, e2 = (execs.execs[i] for i in range(3))
    a = _req(0, 16)
    e0.run_plan(_plan(prefill=[(a, 16)]))
    e1._slot(7)                             # unrelated tenant elsewhere
    free1 = list(e1.free_slots)
    free2 = list(e2.free_slots)
    calls = []
    orig1, orig2 = e1.release, e2.release
    e1.release = lambda rid: (calls.append((1, rid)), orig1(rid))
    e2.release = lambda rid: (calls.append((2, rid)), orig2(rid))
    execs.on_finish(a)
    assert calls == [], "release must only run on the owning executor"
    assert a.rid not in e0.slot_of and len(e0.free_slots) == 2
    assert list(e1.free_slots) == free1
    assert list(e2.free_slots) == free2
    execs.on_finish(a)                      # idempotent for unknown rids


def test_scheduler_turns_slot_exhaustion_into_refusal():
    """End to end: a slot-starved real backend under the model clock must
    surface SlotExhausted as dispatch refusals (requests requeue and retry)
    rather than crashing — and every request still finishes."""
    cfg = get_smoke("deepseek-7b")
    trace = [_req(i, 24, output_len=5) for i in range(10)]
    execs = ClusterRealExecutors(cfg, 2, max_slots=2, max_len=64)
    sim, _ = build_cluster(cfg, "tropical", n_workers=2,
                           worker_spec=WorkerSpec(tp=1),
                           record_decisions=True,
                           backend=execs.as_backend(clock="model"))
    sim.add_trace(copy.deepcopy(trace))
    m = sim.run(until=10000.0)
    assert m.n_finished == len(trace)
    refusals = [d for d in sim.decisions if d[0] == "refuse"]
    assert refusals, "slot starvation must show up as dispatch refusals"
    for _, wid, rid in refusals:
        assert wid in (0, 1) and 0 <= rid < len(trace)


DEVICES_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import copy, json
import jax
from repro.configs import get_smoke
from repro.core.request import Request, SLOSpec
from repro.serving.costmodel import WorkerSpec
from repro.serving.executor import ClusterRealExecutors
from repro.serving.simulator import build_cluster

cfg = get_smoke("qwen2-1.5b")
slo = SLOSpec(ttft=30.0, tpot=5.0)
trace = [Request(rid=i, arrival_time=0.05 * i, prompt_len=20 + 3 * i,
                 output_len=6, slo=slo) for i in range(6)]

def run(devices):
    execs = ClusterRealExecutors(cfg, 2, max_slots=4, max_len=64,
                                 warmup=False, devices=devices)
    sim, _ = build_cluster(cfg, "distserve", n_workers=2,
                           worker_spec=WorkerSpec(tp=1),
                           record_decisions=True,
                           backend=execs.as_backend(clock="model"))
    sim.add_trace(copy.deepcopy(trace))
    m = sim.run(until=3000.0)
    streams = {}
    for e in execs.execs.values():
        for rid, toks in e.generated.items():
            if len(toks) > len(streams.get(rid, [])):
                streams[rid] = list(toks)
    crossing = sum(execs.execs[s].device != execs.execs[d].device
                   for _, s, d in execs.migrations)
    return m.n_finished, sim.decisions, streams, execs.migrations, crossing

two = run(jax.devices())
one = run(jax.devices()[:1])
print(json.dumps({"finished": [two[0], one[0]],
                  "decisions_equal": two[1] == one[1],
                  "tokens_equal": two[2] == one[2],
                  "migrations": len(two[3]), "crossing": two[4],
                  "crossing_one": one[4]}))
"""


def test_workers_on_two_devices_match_one_device():
    """Worker i runs on device i; a migration moves the KV slot row across
    devices (device_put + the destination's in-place write). Under the
    cost-model clock the two-device cluster must make the same decisions
    and generate the same tokens as the same cluster on one device. Runs
    in a subprocess with two forced host devices (this process keeps
    one)."""
    import json
    import os
    import pathlib
    import subprocess
    import sys

    env = dict(os.environ)
    src = str(pathlib.Path(__file__).resolve().parents[1] / "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-c", DEVICES_SCRIPT],
                         capture_output=True, text=True, timeout=300,
                         env=env)
    assert res.returncode == 0, res.stderr[-2000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["finished"] == [6, 6]
    assert out["decisions_equal"] and out["tokens_equal"]
    assert out["migrations"] > 0
    assert out["crossing"] == out["migrations"]
    assert out["crossing_one"] == 0
