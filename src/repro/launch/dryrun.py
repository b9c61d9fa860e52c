import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# NOTE: the two lines above must run before ANY other import — jax locks the
# device count on first init (see MULTI-POD DRY-RUN requirements).

DOC = """Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell and record memory / cost / collective analysis.

This proves the distribution config is coherent without hardware: sharding
mismatches, OOM-scale buffers and unsupported collectives all surface as
compile failures here.

Usage:
  python -m repro.launch.dryrun --arch deepseek-7b --shape decode_32k --mesh single
  python -m repro.launch.dryrun --all --mesh both        # full sweep
Results: experiments/dryrun/<arch>__<shape>__<mesh>.json
"""

import argparse
import dataclasses
import gc
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, list_archs
from repro.launch import hloanalysis
from repro.launch.mesh import make_production_mesh
from repro.models import api as model_api
from repro.models import sharding
from repro.models.moe import EPInfo
from repro.train import optimizer

SHAPES = {
    "train_4k": dict(kind="train", seq=4096, batch=256),
    "prefill_32k": dict(kind="prefill", seq=32768, batch=32),
    "decode_32k": dict(kind="decode", seq=32768, batch=128),
    "long_500k": dict(kind="decode", seq=524288, batch=1),
}

# long_500k needs sub-quadratic context handling (DESIGN.md §5)
LONG_OK = {"rwkv6-7b", "zamba2-2.7b", "gemma2-2b",
           "rwkv6_7b", "zamba2_2_7b", "gemma2_2b"}
ASSIGNED = [a for a in list_archs() if a != "internlm20b"]

WHISPER_DEC_TRAIN = 512     # decoder tokens for encdec train cells
WHISPER_DEC_PREFILL = 64
WHISPER_ENC_DECODE = 1504   # encoder frames backing a decode-cell cross-KV


def _dryrun_cfg(cfg, kind: str):
    """Dry-run variant: layers are UNROLLED so per-layer GEMMs and
    collectives are exact in XLA's static cost model; long-sequence
    attention keeps its Q-chunk *scan* (unrolling 26-61 layers x 32 chunks
    is compile-time prohibitive) and the under-count is repaired by
    hloanalysis.scan_correction ((trip-1) x body dot cost, parsed from the
    compiled HLO). MoE expert tables are padded to divide the 512-chip EP
    group (padded experts get -inf router logits)."""
    return dataclasses.replace(
        cfg, scan_layers=True, attn_unroll_chunks=False, attn_q_chunk=1024,
        expert_pad_to=512 if cfg.is_moe else 0)


def input_specs(cfg, shape_name: str, rules):
    """ShapeDtypeStruct stand-ins + PartitionSpecs for every model input."""
    info = SHAPES[shape_name]
    kind, seq, batch = info["kind"], info["seq"], info["batch"]
    b = sharding.batch_spec(rules, batch)
    i32, bf16 = jnp.int32, cfg.dtype
    sds = jax.ShapeDtypeStruct

    if kind == "train":
        if cfg.family == "encdec":
            d = WHISPER_DEC_TRAIN
            batch_tree = {
                "frames": sds((batch, seq, cfg.d_model), bf16),
                "tokens": sds((batch, d), i32),
                "labels": sds((batch, d), i32),
            }
            spec_tree = {
                "frames": P(b, None, None),
                "tokens": P(b, None), "labels": P(b, None),
            }
        elif cfg.family == "vlm":
            txt = seq - cfg.num_patches
            batch_tree = {
                "tokens": sds((batch, txt), i32),
                "labels": sds((batch, txt), i32),
                "prefix_embeds": sds((batch, cfg.num_patches,
                                      cfg.vision_feature_dim), bf16),
            }
            spec_tree = {
                "tokens": P(b, None), "labels": P(b, None),
                "prefix_embeds": P(b, None, None),
            }
        else:
            batch_tree = {
                "tokens": sds((batch, seq), i32),
                "labels": sds((batch, seq), i32),
            }
            spec_tree = {"tokens": P(b, None), "labels": P(b, None)}
        return batch_tree, spec_tree

    lengths = sds((batch,), i32)
    lspec = P(b)
    if kind == "prefill":
        if cfg.family == "encdec":
            ins = {
                "frames": sds((batch, seq, cfg.d_model), bf16),
                "tokens": sds((batch, WHISPER_DEC_PREFILL), i32),
                "lengths": lengths,
            }
            specs = {"frames": P(b, None, None), "tokens": P(b, None),
                     "lengths": lspec}
        elif cfg.family == "vlm":
            ins = {
                "tokens": sds((batch, seq - cfg.num_patches), i32),
                "prefix_embeds": sds((batch, cfg.num_patches,
                                      cfg.vision_feature_dim), bf16),
                "lengths": lengths,
            }
            specs = {"tokens": P(b, None), "prefix_embeds": P(b, None, None),
                     "lengths": lspec}
        else:
            ins = {"tokens": sds((batch, seq), i32), "lengths": lengths}
            specs = {"tokens": P(b, None), "lengths": lspec}
        return ins, specs

    # decode
    ins = {"tokens": sds((batch,), i32), "lengths": lengths}
    specs = {"tokens": P(b), "lengths": lspec}
    return ins, specs


def _cache_for(cfg, api, shape_name: str, rules):
    info = SHAPES[shape_name]
    kind, seq, batch = info["kind"], info["seq"], info["batch"]
    if kind == "train":
        return None, None
    if cfg.family == "encdec":
        if kind == "prefill":
            tree = api.cache_spec(batch, 2 * WHISPER_DEC_PREFILL, enc_len=seq)
        else:
            tree = api.cache_spec(batch, seq, enc_len=WHISPER_ENC_DECODE)
    else:
        max_len = seq if kind == "decode" else seq
        tree = api.cache_spec(batch, max_len)
    specs = sharding.cache_specs(cfg, rules, batch, tree)
    return tree, specs


def _ep_for(cfg, mesh, rules):
    if not cfg.is_moe:
        return None
    return EPInfo(mesh=mesh, ep_axes=tuple(mesh.axis_names),
                  batch_axes=rules.batch_axes,
                  capacity_factor=cfg.moe_capacity_factor)


def build_step(cfg, api, kind: str, mesh, rules):
    ep = _ep_for(cfg, mesh, rules)
    if kind == "train":
        loss_fn = lambda p, b: api.loss(p, b, ep=ep)
        return optimizer.make_train_step(loss_fn)
    if kind == "prefill":
        if cfg.family == "encdec":
            def step(params, cache, frames, tokens, lengths):
                return api.prefill(params, cache,
                                   {"frames": frames, "tokens": tokens},
                                   lengths, ep=ep)
        elif cfg.family == "vlm":
            def step(params, cache, tokens, prefix_embeds, lengths):
                return api.prefill(params, cache,
                                   {"tokens": tokens,
                                    "prefix_embeds": prefix_embeds},
                                   lengths, ep=ep)
        else:
            def step(params, cache, tokens, lengths):
                return api.prefill(params, cache, tokens, lengths, ep=ep)
        return step

    def step(params, cache, tokens, lengths):
        return api.decode(params, cache, tokens, lengths, ep=ep)
    return step


def run_cell(arch: str, shape_name: str, mesh_kind: str, outdir: Path,
             force: bool = False) -> dict:
    out_path = outdir / f"{arch}__{shape_name}__{mesh_kind}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())

    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "status": "error"}
    if shape_name == "long_500k" and arch not in LONG_OK:
        rec["status"] = "skipped"
        rec["reason"] = ("pure full-attention arch: 512k KV on every layer; "
                         "sub-quadratic archs only (DESIGN.md §5)")
        out_path.write_text(json.dumps(rec, indent=1))
        return rec

    info = SHAPES[shape_name]
    kind, seq, batch = info["kind"], info["seq"], info["batch"]
    cfg = _dryrun_cfg(get_config(arch), kind)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    jax.set_mesh(mesh)
    n_dev = mesh.devices.size
    rules = sharding.make_rules(mesh)
    api = model_api.build(cfg)

    try:
        t0 = time.time()
        params_shape = jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0)))
        pspecs = sharding.param_specs(cfg, params_shape, rules)
        ins, ispecs = input_specs(cfg, shape_name, rules)
        step = build_step(cfg, api, kind, mesh, rules)

        if kind == "train":
            opt_shape = jax.eval_shape(optimizer.init_state, params_shape)
            ospecs = optimizer.state_specs(
                pspecs, params_shape, zero_size=int(mesh.shape["data"]))
            jitted = jax.jit(
                step,
                in_shardings=(pspecs, ospecs, ispecs),
                out_shardings=(pspecs, ospecs, P()),
                donate_argnums=(0, 1),
            )
            lowered = jitted.lower(params_shape, opt_shape, ins)
        else:
            cache_shape, cspecs = _cache_for(cfg, api, shape_name, rules)
            arg_order = list(ins.keys())
            in_sh = (pspecs, cspecs) + tuple(ispecs[k] for k in arg_order)
            logits_spec = P(sharding.batch_spec(rules, batch), None)
            jitted = jax.jit(
                step,
                in_shardings=in_sh,
                out_shardings=(logits_spec, cspecs),
                donate_argnums=(1,),
            )
            lowered = jitted.lower(params_shape, cache_shape,
                                   *[ins[k] for k in arg_order])
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()

        mem = compiled.memory_analysis()
        cost = dict(compiled.cost_analysis())
        hlo = compiled.as_text()
        mc = hloanalysis.module_cost(hlo)
        coll = mc["collectives"]
        dot = {"flops": mc["flops"], "bytes": mc["bytes"],
               "loops": [None] * mc["n_multiplied_blocks"]}
        resident = float((getattr(mem, "argument_size_in_bytes", 0) or 0)
                         + (getattr(mem, "output_size_in_bytes", 0) or 0)
                         - (getattr(mem, "alias_size_in_bytes", 0) or 0))
        dec_len = WHISPER_DEC_TRAIN if kind == "train" else WHISPER_DEC_PREFILL
        mflops = hloanalysis.model_flops(cfg, kind, batch, seq, dec_len)
        rl = hloanalysis.roofline(dot, resident, coll, mflops, n_dev)

        rec.update(
            status="ok", lower_s=round(t1 - t0, 2),
            compile_s=round(t2 - t1, 2), n_devices=n_dev,
            memory={
                k: getattr(mem, k, None) for k in (
                    "argument_size_in_bytes", "output_size_in_bytes",
                    "temp_size_in_bytes", "alias_size_in_bytes",
                    "generated_code_size_in_bytes")
            },
            cost={"xla_cpu_flops_raw": cost.get("flops", 0.0),
                  "xla_cpu_bytes_raw": cost.get("bytes accessed", 0.0),
                  "dot_flops": dot["flops"], "dot_bytes": dot["bytes"],
                  "n_corrected_loops": len(dot["loops"])},
            collectives={k: v for k, v in coll.items() if k != "counts"},
            collective_counts=coll["counts"],
            model_flops_total=mflops,
            roofline=rl.row(),
        )
        # memory_analysis() reports PER-DEVICE sizes (verified: the donated
        # cache slice == alias bytes). Caveat (EXPERIMENTS.md §Methodology):
        # XLA-CPU upcasts every bf16 dot operand to f32, so temp bytes
        # include converts that do not exist on TPU (native bf16 MXU) —
        # steady-state (args+out-alias: weights, caches, optimizer) is the
        # capacity-critical number and is exact.
        args = rec["memory"]["argument_size_in_bytes"] or 0
        temps = rec["memory"]["temp_size_in_bytes"] or 0
        outs = rec["memory"]["output_size_in_bytes"] or 0
        alias = rec["memory"]["alias_size_in_bytes"] or 0
        rec["bytes_per_device"] = float(args + outs - alias)
        rec["bytes_per_device_incl_cpu_temps"] = float(
            args + temps + outs - alias)
        print(f"[dryrun] {arch} {shape_name} {mesh_kind}: OK "
              f"compile={rec['compile_s']}s "
              f"dotflops/dev={rec['cost']['dot_flops']:.3e} "
              f"coll={rec['collectives']['wire_total']:.3e}B "
              f"dom={rec['roofline']['dominant']} "
              f"rf={rec['roofline']['roofline_fraction']:.3f} "
              f"mem/dev={rec['bytes_per_device']/1e9:.2f}GB")
    except Exception as e:  # noqa: BLE001 — record and continue the sweep
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        print(f"[dryrun] {arch} {shape_name} {mesh_kind}: "
              f"FAIL {rec['error'][:200]}")
    out_path.write_text(json.dumps(rec, indent=1))
    del api
    gc.collect()
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    archs = ASSIGNED if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    n_ok = n_fail = n_skip = 0
    for arch in archs:
        for shape in shapes:
            for mk in meshes:
                rec = run_cell(arch, shape, mk, outdir, force=args.force)
                n_ok += rec["status"] == "ok"
                n_fail += rec["status"] == "error"
                n_skip += rec["status"] == "skipped"
    print(f"[dryrun] done: {n_ok} ok, {n_fail} failed, {n_skip} skipped")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
