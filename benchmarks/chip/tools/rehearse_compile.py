"""Compile each configuration's served entries for a described TPU v5e.

    JAX_PLATFORMS=cpu python3 -m benchmarks.chip.tools.rehearse_compile \
        qwen2-1.5b internlm2-20b.pp8-stage

For each configuration, at its cache geometry (``deployment.max_slots`` x
``deployment.max_len``): the program's decode entry and its 1-row prefill
entry at the largest bucket (``ExecutorKernels``), and the reference's
``gaps`` at the longest sequence it checks. Prints ``memory_analysis()``
of each, and the bytes the served state holds beside it (weights and one
cache), so that the slot counts are checked before any chip time is
spent. Nothing runs: a compile that passes is not a chip run.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]


def _gb(n: float) -> str:
    return f"{n / 1e9:.3f} GB"


def rehearse(name: str, one_chip) -> None:
    import jax
    import jax.numpy as jnp

    from benchmarks.chip import program
    from benchmarks.chip.check import bucket
    from benchmarks.chip.reference import dense_lm
    program.importable()
    from repro.models import api as model_api
    from repro.serving.executor import ExecutorKernels

    cfg = json.loads((HERE / "configs" / f"{name}.json").read_text())
    dep = cfg["deployment"]
    slots, max_len = dep["max_slots"], dep["max_len"]
    mc = program.model_config(cfg, jnp.dtype(dep["dtype"]))
    api = model_api.build(mc)
    kernels = ExecutorKernels(api, slots, max_len)

    def placed(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = placed(jax.eval_shape(api.init, jax.random.PRNGKey(0)))
    cache = placed(api.cache_spec(slots, max_len))
    i32 = jnp.int32
    p_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    c_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    print(f"== {name}: {slots} slots x {max_len}; weights {_gb(p_bytes)}, "
          f"cache {_gb(c_bytes)}, together {_gb(p_bytes + c_bytes)}",
          flush=True)

    def show(label, compiled):
        m = compiled.memory_analysis()
        print(f"{label}: args {_gb(m.argument_size_in_bytes)} out "
              f"{_gb(m.output_size_in_bytes)} alias "
              f"{_gb(m.alias_size_in_bytes)} temp "
              f"{_gb(m.temp_size_in_bytes)}", flush=True)

    vec = jax.ShapeDtypeStruct((slots,), i32, sharding=one_chip)
    show("decode", kernels.decode_fn.lower(params, cache, vec, vec).compile())
    b = kernels.buckets[-1]
    row = jax.ShapeDtypeStruct((1,), i32, sharding=one_chip)
    chunk = jax.ShapeDtypeStruct((1, b), i32, sharding=one_chip)
    show(f"prefill bucket {b} x 1 row", kernels.prefill_fn(b, 1).lower(
        params, cache, chunk, row, row, row).compile())

    spec = dense_lm.spec_of(cfg)
    t = bucket(max_len - 1)
    rparams = placed(jax.eval_shape(
        lambda k: dense_lm.init_params(k, spec, jnp.dtype(dep["dtype"])),
        jax.random.PRNGKey(0)))
    toks = jax.ShapeDtypeStruct((t,), i32, sharding=one_chip)
    targets = jax.ShapeDtypeStruct((2, t), i32, sharding=one_chip)
    for low in (False, True):
        show(f"reference gaps T={t} {'fp8 control' if low else 'f32'}",
             jax.jit(dense_lm.gaps, static_argnums=(3, 4)).lower(
                 rparams, toks, targets, spec, low).compile())


def main(argv=None) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(HERE.parents[1]))
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    for name in (argv if argv is not None else sys.argv[1:]):
        rehearse(name, one_chip)
    return 0


if __name__ == "__main__":
    sys.exit(main())
