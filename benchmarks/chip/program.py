"""The system under test, as the benchmark drives it.

Everything the harness takes from the program goes through here: its
model config built from a configuration file, the served cluster
(``ClusterRealExecutors`` under ``build_cluster``'s scheduler, serving
the benchmark's weights, with the program's own warmup), its ``Request``
objects, and the tokens it served.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
SRC = ROOT / "src"
CACHE = ROOT / ".jax_cache"


def importable() -> None:
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


def use_compile_cache() -> None:
    """JAX's persistent compilation cache at the fixed ``<checkout>/
    .jax_cache``, every program cached (so a cell's second run compiles
    nothing). Call before JAX is first imported."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    CACHE.mkdir(exist_ok=True)
    importable()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # no eviction: it needs an access-time file beside every entry, and an
    # entry without one (written with eviction off) makes every write fail
    jax.config.update("jax_compilation_cache_max_size", -1)


def model_config(cfg: dict, dtype):
    """The program's ``ModelConfig`` for a configuration file."""
    importable()
    from repro.models.layers import ModelConfig

    from benchmarks.chip.reference.dense_lm import spec_of
    s = spec_of(cfg)
    return ModelConfig(
        name=cfg["name"], family="dense", num_layers=s.layers,
        d_model=s.d_model, num_heads=s.heads, num_kv_heads=s.kv_heads,
        head_dim=s.head_dim, d_ff=s.d_ff, vocab_size=s.vocab,
        qkv_bias=s.qkv_bias, rope_theta=s.rope_theta, norm_eps=s.eps,
        tie_embeddings=s.tied, dtype=dtype)


def serve(cfg: dict, traffic: dict, seed: int, devices, dtype):
    """(scheduler, executors): ``traffic['workers']`` workers, worker ``i``
    on ``devices[i % len(devices)]``, serving the benchmark's weights for
    ``seed`` (made on the device in one jitted call, copied to each
    device), warmed by the program over its bucket grid."""
    import gc

    import jax
    importable()
    from repro.perf import WorkerSpec
    from repro.serving.executor import ClusterRealExecutors
    from repro.serving.simulator import build_cluster

    from benchmarks.chip.reference.dense_lm import init_params, spec_of
    mc = model_config(cfg, dtype)
    dep = cfg["deployment"]
    execs = ClusterRealExecutors(
        mc, traffic["workers"], rng=jax.random.PRNGKey(seed),
        max_slots=dep["max_slots"], max_len=dep["max_len"],
        devices=list(devices), warmup=False)
    # the program made weights of its own: drop them before making ours
    for e in execs.execs.values():
        e.params = None
    gc.collect()
    params = jax.jit(init_params, static_argnums=(1, 2))(
        jax.random.PRNGKey(seed), spec_of(cfg), dtype)
    placed = {}
    for e in execs.execs.values():
        if e.device not in placed:
            placed[e.device] = jax.device_put(params, e.device)
            execs.kernels.warmup(placed[e.device], e.device)
        e.params = placed[e.device]
    del params
    sim, _ = build_cluster(mc, traffic["policy"],
                           n_workers=traffic["workers"],
                           worker_spec=WorkerSpec(tp=1),
                           backend=execs.as_backend(clock="wall"))
    return sim.sched, execs


def requests(planned, slo: dict) -> list:
    """The program's ``Request`` for each planned one: ``arrival_time`` is
    its due time, its SLO the cell's limits for its prompt length."""
    importable()
    from repro.core.request import Request, SLOClass

    from benchmarks.chip.stats import ttft_limit
    return [Request(rid=p.rid, arrival_time=p.due, prompt_len=p.prompt_len,
                    output_len=p.output_len,
                    slo=SLOClass(ttft=ttft_limit(slo, p.prompt_len),
                                 tpot=slo["tpot_s"]))
            for p in planned]


def served_tokens(execs) -> dict[int, list[int]]:
    """Every request's token stream as the executors produced it (a
    migrated request's destination holds the whole stream)."""
    out: dict[int, list[int]] = {}
    for e in execs.execs.values():
        for rid, toks in e.generated.items():
            if len(toks) > len(out.get(rid, [])):
                out[rid] = list(toks)
    return out
