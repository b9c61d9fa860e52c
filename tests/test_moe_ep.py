"""EP shard_map path vs dense reference oracle — runs in a subprocess with
8 forced host devices (the main pytest process must keep 1 device)."""
import json
import os
import pathlib
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import make_mesh, set_mesh
    from jax.sharding import AxisType
    from repro.models.layers import ModelConfig
    from repro.models import moe as M

    mesh = make_mesh((2, 4), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
    set_mesh(mesh)
    cfg = ModelConfig(name="moe-test", family="moe", num_layers=1,
                      d_model=64, num_heads=4, num_kv_heads=4, head_dim=16,
                      d_ff=96, vocab_size=128, num_experts=6, top_k=2,
                      expert_pad_to=8, moe_capacity_factor=4.0,
                      dtype=jnp.float32)
    params = M.init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 64), jnp.float32)

    ref = M.apply_moe_reference(params, x, cfg)
    info = M.EPInfo(mesh=mesh, ep_axes=("data", "model"),
                    batch_axes=("data",), capacity_factor=4.0)
    ep_fn = jax.jit(lambda p, xx: M.apply_moe_ep(p, xx, cfg, info))
    out = ep_fn(params, x)
    err = float(jnp.abs(out - ref).max())
    rel = err / float(jnp.abs(ref).max())
    info_f = M.EPInfo(mesh=mesh, ep_axes=("data", "model"),
                      batch_axes=("data",), capacity_factor=4.0,
                      fused_a2a=True)
    f_fn = jax.jit(lambda p, xx: M.apply_moe_ep(p, xx, cfg, info_f))
    out_f = f_fn(params, x)
    rel_fused = float(jnp.abs(out_f - out).max()) / float(jnp.abs(ref).max())
    info_ag = M.EPInfo(mesh=mesh, ep_axes=("data", "model"),
                       batch_axes=("data",), ep_mode="allgather")
    ag_fn = jax.jit(lambda p, xx: M.apply_moe_ep(p, xx, cfg, info_ag))
    out_ag = ag_fn(params, x)
    err_ag = float(jnp.abs(out_ag - ref).max())
    rel_ag = err_ag / float(jnp.abs(ref).max())
    print(json.dumps({"err": err, "rel": rel, "rel_ag": rel_ag,
                      "rel_fused": rel_fused}))
""")


def test_ep_matches_reference():
    # the subprocess doesn't see pytest's pyproject pythonpath insertion
    env = dict(os.environ)
    src = str(pathlib.Path(__file__).resolve().parents[1] / "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        timeout=420, env=env,
    )
    assert res.returncode == 0, res.stderr[-2000:]
    data = json.loads(res.stdout.strip().splitlines()[-1])
    # capacity_factor=4 on tiny batches still drops a little; the surviving
    # tokens must match closely
    assert data["rel"] < 5e-2, data
    # allgather mode has NO capacity drops: must match the oracle tightly
    assert data["rel_ag"] < 1e-4, data
    # fused all_to_all must be bit-identical routing vs per-axis composition
    assert data["rel_fused"] < 1e-5, data
