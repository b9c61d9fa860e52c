"""Bring-up smoke run of the served path on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the cross-chip migration path only

Everything runs in this one process, the only one that touches JAX. When
JAX finds no TPU it exits 1 without a result line.

One chip, qwen2-1.5b at its published config in bf16, random weights
from a seed:

* kernels — both Pallas attention kernels through their ``kernels.ops``
  wrappers (``auto_interpret`` must pick compiled Mosaic here:
  ``tpu_custom_call`` in the HLO) at qwen2 widths, against
  ``repro.kernels.ref``; then ``calibrate_hardware`` over them, which
  must accept the device kind.
* prefill — the fused ``ExecutorKernels.prefill_fn`` entry's last-token
  logits against a plain ``api.prefill`` of the same prompt.
* serve — ``repro.launch.serve.main(--mode real ...)``: the tropical
  policy through ``ClusterScheduler`` + ``RealJaxBackend`` on the
  mooncake mix; every request must finish.

``--chips 4`` runs only the multi-chip path and what it is compared
with: tropical on four workers, one per chip, under the cost-model clock,
then the same trace with all four workers on chip 0. Scheduling
decisions and token streams must be identical, and KV must have migrated,
every time across chips.

Any failed check raises, so the script exits non-zero without a result
line. On success the last stdout line is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
This is a bring-up check, not a benchmark: the times it prints include
compilation and are not device measurements.
"""
from __future__ import annotations

import argparse
import copy
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen2-1.5b"
SEED = 0
TOL = dict(rtol=2e-2, atol=2e-2)          # bf16, as tests/test_kernels.py
# served run: 19 mooncake requests, 17 of them with prompts >= 512 tokens
SERVE_ARGS = ["--rate", "1.0", "--duration", "20", "--workers", "2",
              "--max-slots", "16", "--max-len", "2048"]
# cross-chip run: cache geometry and trace of the four-worker comparison
CROSS_SLOTS, CROSS_LEN, CROSS_RATE, CROSS_DURATION = 8, 1024, 1.0, 20.0


def say(key: str, value) -> None:
    print(f"{key}: {value}", flush=True)


class CompileClock:
    """Sums JAX's backend-compile events (persistent-cache reads
    included) over the process."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1


def _close(name: str, got, want) -> None:
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if not np.isfinite(got).all():
        raise AssertionError(f"{name}: non-finite output")
    say(f"{name}_max_abs_err", float(np.abs(got - want).max()))
    np.testing.assert_allclose(got, want, err_msg=name, **TOL)


def _compiled(name: str, fn, *args):
    """Compile the jitted ``fn`` for the chip, require a Pallas kernel in
    the program, and run it."""
    compiled = fn.lower(*args).compile()
    if "tpu_custom_call" not in compiled.as_text():
        raise AssertionError(f"{name}: no tpu_custom_call in the HLO")
    say(f"{name}_tpu_custom_call", True)
    return compiled(*args)


def phase_kernels(cfg) -> None:
    """Both kernels compiled at the model's attention widths, against the
    jnp oracles, at the page and chunk sizes calibration uses."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref
    from repro.perf import V5E
    from repro.perf.calibrate import calibrate_hardware

    hq, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    rng = np.random.default_rng(SEED)
    bf = jnp.bfloat16

    b, ps, mp = 4, 16, 8
    n_pages = b * mp + 1
    q = jnp.asarray(rng.normal(size=(b, hq, d)), bf)
    kp = jnp.asarray(rng.normal(size=(n_pages, ps, hkv, d)), bf)
    vp = jnp.asarray(rng.normal(size=(n_pages, ps, hkv, d)), bf)
    bt = jnp.asarray(rng.permutation(n_pages)[: b * mp].reshape(b, mp),
                     jnp.int32)
    lengths = jnp.asarray(rng.integers(1, mp * ps + 1, size=(b,)), jnp.int32)
    if ops.auto_interpret():
        raise AssertionError("kernels.ops would interpret on this device")
    out = _compiled("paged_attention", ops.paged_attention_op,
                    q, kp, vp, bt, lengths)
    _close("paged_attention", out,
           ref.paged_attention_ref(q, kp, vp, bt, lengths))

    sq, smax = 256, 512
    q = jnp.asarray(rng.normal(size=(1, sq, hq, d)), bf)
    kc = jnp.asarray(rng.normal(size=(1, smax, hkv, d)), bf)
    vc = jnp.asarray(rng.normal(size=(1, smax, hkv, d)), bf)
    starts = jnp.asarray([smax - sq], jnp.int32)
    out = _compiled("chunked_prefill", ops.chunked_prefill_op,
                    q, kc, vc, starts)
    _close("chunked_prefill", out,
           ref.chunked_prefill_attention_ref(q, kc, vc, starts))

    hw, cal = calibrate_hardware(V5E, interpret=False, repeats=3)
    say("calibrate_device", cal.device)
    for key in ("mfu_prefill", "mfu_decode", "bw_eff"):
        say(f"calibrate_{key}", getattr(hw, key))


def phase_prefill(cfg, prompt_len: int = 512, max_len: int = 1024) -> None:
    """Fused slot-indexed prefill entry vs plain full-prompt prefill."""
    import jax
    import jax.numpy as jnp

    from repro.models import api as model_api
    from repro.serving.executor import ExecutorKernels

    api = model_api.build(cfg)
    params = jax.jit(api.init)(jax.random.PRNGKey(SEED))
    tokens = jax.random.randint(jax.random.PRNGKey(SEED + 1),
                                (1, prompt_len), 0, cfg.vocab_size,
                                dtype=jnp.int32)
    n = jnp.asarray([prompt_len], jnp.int32)
    want, _ = jax.jit(api.prefill)(params, api.init_cache(1, max_len),
                                   tokens, n)
    kernels = ExecutorKernels(api, max_slots=1, max_len=max_len)
    bucket = kernels.bucket_for(prompt_len)
    chunk = jnp.zeros((1, bucket), jnp.int32).at[:, :prompt_len].set(tokens)
    zero = jnp.zeros((1,), jnp.int32)
    _, got, _ = kernels.prefill_fn(bucket, 1)(
        params, api.init_cache(1, max_len), chunk, zero, zero, n)
    say("prefill_prompt_tokens", prompt_len)
    _close("prefill_logits", got, want)
    say("prefill_argmax_agrees",
        bool(jnp.argmax(got[0]) == jnp.argmax(want[0])))


def phase_serve() -> None:
    """The served path through its normal entry point, at full width."""
    from repro.launch import serve

    row = serve.main(["--mode", "real", "--arch", ARCH,
                      "--policy", "tropical", "--scenario", "mooncake",
                      "--seed", str(SEED), *SERVE_ARGS])
    for key in ("platform", "device_kind", "n_total", "n_finished",
                "prompt_tokens", "max_prompt_len", "output_tokens",
                "generated_tokens", "prefill_traces", "decode_traces",
                "migrations", "slo_attainment"):
        say(f"serve_{key}", row[key])
    if row["platform"] != "tpu":
        raise AssertionError(f"served on {row['platform']}, not tpu")
    if row["n_total"] < 10 or row["n_finished"] != row["n_total"]:
        raise AssertionError(
            f"finished {row['n_finished']} of {row['n_total']} requests")
    if row["max_prompt_len"] < 512:
        raise AssertionError("no prompt of >= 512 tokens was served")
    if row["generated_tokens"] < row["output_tokens"]:
        raise AssertionError("fewer tokens generated than requested")


def _cross_run(cfg, devices):
    """Tropical on four workers under the cost-model clock; returns the
    metrics, decision log, token streams and executors."""
    import jax

    from repro.launch.serve import clip_to_cache
    from repro.perf import WorkerSpec
    from repro.serving.executor import ClusterRealExecutors
    from repro.serving.simulator import build_cluster
    from repro.workload import generate_trace

    execs = ClusterRealExecutors(cfg, 4, max_slots=CROSS_SLOTS,
                                 max_len=CROSS_LEN,
                                 rng=jax.random.PRNGKey(SEED),
                                 warmup=False, devices=devices)
    sim, cost = build_cluster(cfg, "tropical", n_workers=4,
                              worker_spec=WorkerSpec(tp=1),
                              record_decisions=True,
                              backend=execs.as_backend(clock="model"))
    trace = generate_trace(CROSS_RATE, CROSS_DURATION, cost, seed=SEED)
    clip_to_cache(trace, CROSS_LEN)
    sim.add_trace(copy.deepcopy(trace))
    m = sim.run(until=CROSS_DURATION * 10)
    streams: dict[int, list[int]] = {}
    for e in execs.execs.values():
        for rid, toks in e.generated.items():
            if len(toks) > len(streams.get(rid, [])):
                streams[rid] = list(toks)
    return m, list(sim.decisions), streams, execs


def phase_cross_chip(cfg, n_chips: int = 4) -> None:
    """Four workers on four chips vs the same four workers on chip 0."""
    import jax

    devices = jax.devices()
    if len(devices) < n_chips:
        raise AssertionError(
            f"--chips {n_chips} needs {n_chips} devices, JAX sees "
            f"{len(devices)}")
    spread = _cross_run(cfg, devices[:n_chips])
    m4, dec4, tok4, ex4 = spread
    one = _cross_run(cfg, devices[:1])
    m1, dec1, tok1, _ = one
    placed = sorted({str(e.device) for e in ex4.execs.values()})
    crossing = [(rid, s, d) for rid, s, d in ex4.migrations
                if ex4.execs[s].device != ex4.execs[d].device]
    say("cross_workers_on", placed)
    say("cross_n_total", m4.n_total)
    say("cross_n_finished", [m4.n_finished, m1.n_finished])
    say("cross_decisions", len(dec4))
    say("cross_generated_tokens", sum(len(t) for t in tok4.values()))
    say("cross_migrations", len(ex4.migrations))
    say("cross_migrations_across_chips", len(crossing))
    if len(placed) != n_chips:
        raise AssertionError(f"workers placed on {placed}")
    if not (m4.n_finished == m4.n_total == m1.n_finished == m1.n_total):
        raise AssertionError("not every request finished")
    if dec4 != dec1:
        raise AssertionError("scheduling decisions differ across placements")
    if tok4 != tok1:
        raise AssertionError("token streams differ across placements")
    if not ex4.migrations or len(crossing) != len(ex4.migrations):
        raise AssertionError(
            f"{len(crossing)} of {len(ex4.migrations)} migrations crossed "
            "chips; want all, and at least one")
    say("cross_identical", True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: run only the four-chip migration path")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "nothing was run", file=sys.stderr)
        return 1

    from repro.compile_cache import use_compile_cache
    from repro.configs import get_config

    say("compile_cache", use_compile_cache())
    clock = CompileClock()
    say("device_kind", dev.device_kind)
    say("device_count", len(devices))
    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_cross_chip(cfg)
    else:
        for phase in (lambda: phase_kernels(cfg), lambda: phase_prefill(cfg),
                      phase_serve):
            phase()
            gc.collect()
    say("wall_seconds", time.perf_counter() - t0)
    say("compile_seconds", clock.seconds)
    say("compiles", clock.count)
    for d in devices[:args.chips]:
        stats = d.memory_stats() or {}
        say(f"peak_bytes_in_use[{d.id}]", stats.get("peak_bytes_in_use"))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
