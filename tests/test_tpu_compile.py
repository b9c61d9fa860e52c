"""Compile the served path for a described TPU v5e, without a chip.

The TPU compiler refuses what interpret mode accepts: block shapes off
the (8, 128) tiling, too much VMEM, a program larger than HBM. These
tests lower the Pallas kernels and the served step programs for one chip
of a described ``v5e:2x2`` topology. Nothing runs, so they say nothing
about results or times.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and under several
test workers only the one given this file may do so.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.chunked_prefill import chunked_prefill_attention
from repro.kernels.decode_attention import decode_attention
from repro.kernels.paged_attention import paged_attention
from repro.models import api as model_api
from repro.serving.executor import ExecutorKernels

HBM_BYTES = 16e9
# (hq, hkv, d): qwen2-1.5b first, then every kv-head count in {1, 2, 4}
# with both head dims in {64, 128}
WIDTHS = [(12, 2, 128), (4, 1, 64), (8, 1, 128), (8, 2, 64), (8, 4, 64),
          (16, 4, 128)]
# the page and chunk sizes perf/calibrate.py measures with
PAGE, PAGES_PER_SEQ, BATCH, SEQ = 16, 8, 4, 256
# qwen2-1.5b's served cache: 28 layers, 16 slots x 8192 positions
LAYERS, SLOTS, MAX_LEN = 28, 16, 8192


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _placed(sharding, tree):
    return jax.tree.map(
        lambda a: _sds(sharding, a.shape, a.dtype), tree)


def _kernel_hlo(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("hq,hkv,d", WIDTHS)
def test_paged_attention_compiles_for_v5e(one_chip, hq, hkv, d):
    s = functools.partial(_sds, one_chip)
    n_pages = BATCH * PAGES_PER_SEQ + 1
    hlo = _kernel_hlo(
        functools.partial(paged_attention, interpret=False),
        s((BATCH, hq, d), jnp.bfloat16),
        s((n_pages, PAGE, hkv, d), jnp.bfloat16),
        s((n_pages, PAGE, hkv, d), jnp.bfloat16),
        s((BATCH, PAGES_PER_SEQ), jnp.int32), s((BATCH,), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("hq,hkv,d", WIDTHS)
def test_chunked_prefill_compiles_for_v5e(one_chip, hq, hkv, d):
    s = functools.partial(_sds, one_chip)
    hlo = _kernel_hlo(
        functools.partial(chunked_prefill_attention, interpret=False),
        s((1, SEQ, hq, d), jnp.bfloat16),
        s((1, 2 * SEQ, hkv, d), jnp.bfloat16),
        s((1, 2 * SEQ, hkv, d), jnp.bfloat16), s((1,), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("hq,hkv,d,dtype,softcap", [
    (12, 2, 128, jnp.bfloat16, None),           # qwen2-1.5b, as served
    (12, 2, 128, jnp.float8_e4m3fn, 50.0),
    (32, 32, 128, jnp.bfloat16, None),          # MHA: a smaller block
    (8, 4, 256, jnp.bfloat16, 50.0),            # gemma2
    (32, 32, 64, jnp.bfloat16, None),           # stablelm: the grid form
    (12, 2, 64, jnp.float8_e4m3fn, None),
])
def test_decode_attention_compiles_for_v5e(one_chip, hq, hkv, d, dtype,
                                           softcap):
    """At 16 slots x 8192 positions; qwen2-1.5b's 28 layers at its
    widths, 4 layers elsewhere (the layer count only sizes the array)."""
    s = functools.partial(_sds, one_chip)
    layers = LAYERS if (hq, hkv, d) == (12, 2, 128) else 4
    cache = s((layers, SLOTS, hkv, MAX_LEN, d), dtype)
    i32 = s((), jnp.int32)
    hlo = _kernel_hlo(
        functools.partial(decode_attention, softcap=softcap,
                          interpret=False),
        s((SLOTS, hq, d), jnp.bfloat16), cache, cache, i32, i32,
        s((SLOTS,), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.fixture(scope="module")
def mosaic():
    """The program's kernels compiled as on the chip: the test host's
    default backend is the CPU, where ``auto_interpret`` says interpret."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "auto_interpret", lambda: False)
        yield


def _qwen2(one_chip, max_len):
    api = model_api.build(get_config("qwen2-1.5b"))
    kernels = ExecutorKernels(api, max_slots=SLOTS, max_len=max_len)
    params = _placed(one_chip,
                     jax.eval_shape(api.init, jax.random.PRNGKey(0)))
    cache = _placed(one_chip, api.cache_spec(SLOTS, max_len))
    return kernels, params, cache


@pytest.fixture(scope="module")
def qwen2_step(one_chip, mosaic):
    """qwen2-1.5b at its published config: entry points and the shapes
    of its params and a 16-slot x 2048-position cache, on one chip."""
    return _qwen2(one_chip, 2048)


@pytest.fixture(scope="module")
def qwen2_decode_served(one_chip, mosaic):
    """The decode step compiled at the served 16 x 8192 cache, and that
    cache's shapes."""
    kernels, params, cache = _qwen2(one_chip, MAX_LEN)
    s = functools.partial(_sds, one_chip)
    compiled = kernels.decode_fn.lower(
        params, cache, s((SLOTS,), jnp.int32),
        s((SLOTS,), jnp.int32)).compile()
    return compiled, cache


def _bytes(compiled) -> float:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def test_served_decode_step_compiles_and_fits_one_chip(one_chip, qwen2_step):
    kernels, params, cache = qwen2_step
    s = functools.partial(_sds, one_chip)
    compiled = kernels.decode_fn.lower(
        params, cache, s((16,), jnp.int32), s((16,), jnp.int32)).compile()
    assert _bytes(compiled) < HBM_BYTES


def test_served_decode_step_updates_the_cache_in_place(one_chip, qwen2_step):
    """The decode step writes its rows into the donated cache: the cache
    is aliased from input to output, and the step's scratch holds less
    than one layer's K and V (no layer, let alone the cache, is copied)."""
    kernels, params, cache = qwen2_step
    s = functools.partial(_sds, one_chip)
    m = kernels.decode_fn.lower(
        params, cache, s((16,), jnp.int32),
        s((16,), jnp.int32)).compile().memory_analysis()
    k = cache["k"]
    layer_kv = 2 * k.size // k.shape[0] * k.dtype.itemsize
    assert layer_kv == 2 * 16 * 2048 * 2 * 128 * 2
    assert m.temp_size_in_bytes < layer_kv
    assert m.alias_size_in_bytes == sum(
        a.size * a.dtype.itemsize for a in cache.values())


def test_served_prefill_step_compiles_for_v5e(one_chip, qwen2_step):
    kernels, params, cache = qwen2_step
    s = functools.partial(_sds, one_chip)
    one = s((1,), jnp.int32)
    compiled = kernels.prefill_fn(512, 1).lower(
        params, cache, s((1, 512), jnp.int32), one, one, one).compile()
    assert _bytes(compiled) < HBM_BYTES


def test_served_decode_step_attends_with_the_kernel(qwen2_decode_served):
    compiled, _ = qwen2_decode_served
    assert "tpu_custom_call" in compiled.as_text()
    assert _bytes(compiled) < HBM_BYTES


def test_served_decode_step_keeps_the_cache_in_place(qwen2_decode_served):
    """The kernel reads the carried cache where it lies: the cache stays
    aliased, no op makes a copy of the whole (L, B, Hkv, S, D) K or V,
    and the step's scratch stays under one layer's K and V."""
    compiled, cache = qwen2_decode_served
    k = cache["k"]
    whole = "[" + ",".join(map(str, k.shape)) + "]"
    copies = [line for line in compiled.as_text().splitlines()
              if whole in line.split("=", 1)[-1].split("(", 1)[0]
              and " copy(" in line]
    assert not copies, copies[:2]
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes == sum(
        a.size * a.dtype.itemsize for a in cache.values())
    assert m.temp_size_in_bytes < 2 * k.size // k.shape[0] * \
        k.dtype.itemsize
