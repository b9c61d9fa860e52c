import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

DOC = """Perf hillclimb harness (§Perf): recompile one dry-run cell with a
named variant and report the roofline-term delta vs baseline.

  python -m repro.launch.hillclimb --arch kimi-k2-1t --shape decode_32k \
      --variant ep_floor1

Variants are (cfg, EPInfo, spec) transformations — each encodes one
hypothesis from the §Perf log. Results: experiments/perf/<cell>__<v>.json
"""

import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.launch import hloanalysis
from repro.launch.dryrun import (SHAPES, WHISPER_DEC_PREFILL,
                                 WHISPER_DEC_TRAIN, _cache_for, _dryrun_cfg,
                                 _ep_for, build_step, input_specs)
from repro.launch.mesh import make_production_mesh
from repro.models import api as model_api
from repro.models import sharding
from repro.models.moe import EPInfo
from repro.train import optimizer


# --------------------------------------------------------------- variants

def v_baseline(cfg, ep):
    return cfg, ep


def v_kv_fp8(cfg, ep):
    """Hypothesis: decode is memory-bound on KV reads; fp8 storage halves
    cache bytes -> memory term ~/2 where KV >> weights."""
    return dataclasses.replace(cfg, kv_cache_quant=True), ep


def v_window_cache(cfg, ep):
    """Hypothesis (gemma2): local layers only ever attend within the
    window; a window-sized ring cache removes (S-W)/S of their KV reads
    and memory — halves cache footprint at 32k, ~2x more at 500k.
    (requires unrolled layers: per-layer cache shapes)"""
    return dataclasses.replace(cfg, window_sized_cache=True,
                               scan_layers=False), ep


def v_kv_fp8_window(cfg, ep):
    cfg, ep = v_kv_fp8(cfg, ep)
    return v_window_cache(cfg, ep)


def v_ep_floor1(cfg, ep):
    """Hypothesis (MoE decode): with T_loc*k << E_pad, the capacity floor
    of 4 pads the all_to_all buffers and expert GEMMs 4x; floor 1 cuts EP
    compute and collective bytes ~4x at identical routing semantics."""
    return cfg, dataclasses.replace(ep, capacity_floor=1)


def v_ep_cf1(cfg, ep):
    """Capacity factor 2 -> 1.25: less padding at slightly higher drop
    risk (train-side lever)."""
    return cfg, dataclasses.replace(
        ep, capacity_factor=1.25,
    )


def v_ep_fused_a2a(cfg, ep):
    """Hypothesis: the per-axis all_to_all composition moves the dispatch
    buffer once per mesh axis (2x on a 2-axis EP group); a single fused
    all_to_all halves EP wire bytes."""
    return cfg, dataclasses.replace(ep, fused_a2a=True)


def v_ep_cf1_fused(cfg, ep):
    cfg, ep = v_ep_cf1(cfg, ep)
    return v_ep_fused_a2a(cfg, ep)


def v_ep_train_best(cfg, ep):
    """Stacked winners for MoE train: cf 1.25 + fused a2a + no remat."""
    cfg, ep = v_ep_cf1_fused(cfg, ep)
    return v_remat_none(cfg, ep)


def v_ep_allgather(cfg, ep):
    """Hypothesis (MoE decode, beyond-paper): with T_global tokens << N*C
    padded slots, all_to_all routing is the wrong algorithm — broadcast all
    tokens (O(T*d)), compute local experts masked, psum the contributions
    (O(T*d)). Predicted ~15-20x lower collective volume for kimi decode."""
    return cfg, dataclasses.replace(ep, ep_mode="allgather")


def v_remat_none(cfg, ep):
    """Hypothesis (train): dots-saveable remat re-runs every block matmul
    in bwd (+~30% dot flops); disabling remat trades memory for compute."""
    return dataclasses.replace(cfg, remat_policy="none"), ep


VARIANTS = {
    "baseline": v_baseline,
    "kv_fp8": v_kv_fp8,
    "window_cache": v_window_cache,
    "kv_fp8_window": v_kv_fp8_window,
    "ep_floor1": v_ep_floor1,
    "ep_cf1": v_ep_cf1,
    "ep_fused_a2a": v_ep_fused_a2a,
    "ep_cf1_fused": v_ep_cf1_fused,
    "ep_train_best": v_ep_train_best,
    "ep_allgather": v_ep_allgather,
    "remat_none": v_remat_none,
}


def run_variant(arch: str, shape_name: str, variant: str,
                outdir: Path = Path("experiments/perf"),
                mesh_kind: str = "single") -> dict:
    outdir.mkdir(parents=True, exist_ok=True)
    out_path = outdir / f"{arch}__{shape_name}__{variant}.json"

    info = SHAPES[shape_name]
    kind, seq, batch = info["kind"], info["seq"], info["batch"]
    cfg = _dryrun_cfg(get_config(arch), kind)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    jax.set_mesh(mesh)
    rules = sharding.make_rules(mesh)
    ep = _ep_for(cfg, mesh, rules)
    cfg, ep = VARIANTS[variant](cfg, ep)

    rec = {"arch": arch, "shape": shape_name, "variant": variant,
           "status": "error"}
    try:
        api = model_api.build(cfg)
        t0 = time.time()
        params_shape = jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0)))
        pspecs = sharding.param_specs(cfg, params_shape, rules)
        ins, ispecs = input_specs(cfg, shape_name, rules)

        # step with the (possibly modified) ep
        if kind == "train":
            loss_fn = lambda p, b: api.loss(p, b, ep=ep)
            step = optimizer.make_train_step(loss_fn)
            opt_shape = jax.eval_shape(optimizer.init_state, params_shape)
            ospecs = optimizer.state_specs(
                pspecs, params_shape, zero_size=int(mesh.shape["data"]))
            jitted = jax.jit(step, in_shardings=(pspecs, ospecs, ispecs),
                             out_shardings=(pspecs, ospecs, P()),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(params_shape, opt_shape, ins)
        else:
            cache_shape, cspecs = _cache_for(cfg, api, shape_name, rules)
            if kind == "prefill":
                def step(params, cache, tokens, lengths):
                    return api.prefill(params, cache, tokens, lengths, ep=ep)
            else:
                def step(params, cache, tokens, lengths):
                    return api.decode(params, cache, tokens, lengths, ep=ep)
            order = list(ins.keys())
            jitted = jax.jit(
                step,
                in_shardings=(pspecs, cspecs) + tuple(ispecs[k] for k in order),
                out_shardings=(P(sharding.batch_spec(rules, batch), None),
                               cspecs),
                donate_argnums=(1,))
            lowered = jitted.lower(params_shape, cache_shape,
                                   *[ins[k] for k in order])
        compiled = lowered.compile()
        t1 = time.time()

        mem = compiled.memory_analysis()
        hlo = compiled.as_text()
        mc = hloanalysis.module_cost(hlo)
        coll = mc["collectives"]
        dot = {"flops": mc["flops"], "bytes": mc["bytes"]}
        resident = float(mem.argument_size_in_bytes
                         + mem.output_size_in_bytes
                         - mem.alias_size_in_bytes)
        dec_len = WHISPER_DEC_TRAIN if kind == "train" else WHISPER_DEC_PREFILL
        mflops = hloanalysis.model_flops(cfg, kind, batch, seq, dec_len)
        rl = hloanalysis.roofline(dot, resident, coll, mflops,
                                  mesh.devices.size)
        rec.update(status="ok", compile_s=round(t1 - t0, 2),
                   roofline=rl.row(),
                   bytes_per_device=resident,
                   collectives={"wire_total": coll["wire_total"],
                                "total": coll["total"]})
        print(f"[hillclimb] {arch} {shape_name} {variant}: "
              f"step={rl.step_s*1e3:.2f}ms dom={rl.dominant} "
              f"c/m/coll={rl.compute_s*1e3:.2f}/{rl.memory_s*1e3:.2f}/"
              f"{rl.collective_s*1e3:.2f}ms rf={rl.roofline_fraction:.3f} "
              f"mem={resident/1e9:.2f}GB")
    except Exception as e:  # noqa: BLE001
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-3000:]
        print(f"[hillclimb] {arch} {shape_name} {variant}: FAIL "
              f"{rec['error'][:200]}")
    out_path.write_text(json.dumps(rec, indent=1))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--variant", required=True,
                    help=",".join(VARIANTS) + " (comma-separated ok)")
    ap.add_argument("--mesh", default="single")
    args = ap.parse_args()
    for v in args.variant.split(","):
        run_variant(args.arch, args.shape, v, mesh_kind=args.mesh)


if __name__ == "__main__":
    main()
