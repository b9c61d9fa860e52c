"""Measured-MFU roofline: instantiate the analytic model from real kernels.

The analytic ``CostModel`` ships with assumed efficiency constants
(``mfu_prefill``/``mfu_decode``/``bw_eff``). A real deployment should not
trust them: achieved MFU depends on head dims, page sizes, XLA version
and the exact kernels in the serving path. ``calibrate_hardware`` runs
the repo's own Pallas kernels — ``kernels/chunked_prefill.py`` for the
prefill side, ``kernels/paged_attention.py`` for the decode side — once
at startup, times them, and returns a ``HardwareSpec`` whose efficiency
constants are *measurements*:

    mfu    = achieved_flops / (elapsed · peak_flops)
    bw_eff = achieved_bytes / (elapsed · hbm_bw)

``calibrate_interference`` (v2) extends the same measured-constants idea
to the §IV mixed-batch contention coefficient: it runs the two kernels
*mixed* vs *pure* across a (decode-batch × chunk-size) grid and solves
each cell's measured excess for γ, returning a bucketed
``InterferenceTable`` that drops into ``HardwareSpec.interference``
(the scalar stays accepted as the degenerate 1×1 table).

``CalibratedRooflineBackend`` is the ``ExecutionBackend`` over the
resulting model: the ROADMAP's "batched roofline with measured MFU"
backend. Off-TPU (CPU CI, interpret-mode Pallas) the measured fractions
are tiny but still well-defined — they are clamped into ``(0, 1]`` and
the backend remains exercisable end-to-end. Compiled, the kernels run on
the device the ``HardwareSpec`` describes: a device whose ``device_kind``
the spec does not list raises (its peaks are unknown), and so does a
fraction above ``_FRAC_CEIL`` (no chip beats its own peak; such a reading
means the flop/byte accounting or the peak is wrong).
"""
from __future__ import annotations

import dataclasses
import statistics
import time
from typing import Optional

from repro.perf.hardware import (HardwareSpec, InterferenceTable, V5E,
                                 WorkerSpec)
from repro.perf.model import CostModel

_MFU_FLOOR = 1e-6        # interpret-mode measurements stay valid fractions
_FRAC_CEIL = 1.05        # compiled readings above this are accounting bugs


@dataclasses.dataclass(frozen=True)
class KernelCalibration:
    """What the calibration run measured (seconds + derived fractions).

    The ``gemm_*`` fields (0.0 when the GEMM pass is disabled) time the
    full-layer dense forward — projections, MLP, unembed — so the blended
    ``mfu_prefill``/``mfu_decode`` cover the MLP-dominated regime the
    attention microkernels alone cannot see."""
    mfu_prefill: float
    mfu_decode: float
    bw_eff: float
    prefill_seconds: float
    decode_seconds: float
    prefill_flops: float
    decode_flops: float
    decode_bytes: float
    device: str
    gemm_prefill_seconds: float = 0.0
    gemm_decode_seconds: float = 0.0
    gemm_prefill_flops: float = 0.0
    gemm_decode_flops: float = 0.0
    mfu_gemm_prefill: float = 0.0
    mfu_gemm_decode: float = 0.0


def _frac(x: float, interpret: bool, what: str) -> float:
    """A measured fraction of a peak. Interpret mode keeps it a valid
    fraction in ``[_MFU_FLOOR, 1]``; compiled, timing noise up to
    ``_FRAC_CEIL`` reads as 1 and anything above raises."""
    if interpret:
        return min(max(x, _MFU_FLOOR), 1.0)
    if x > _FRAC_CEIL:
        raise ValueError(
            f"measured {what} = {x:.3f} of peak exceeds {_FRAC_CEIL}: the "
            "flop/byte accounting or the HardwareSpec peak is wrong")
    return min(x, 1.0)


def _check_device(hw: HardwareSpec, interpret: bool) -> None:
    """Compiled, the kernels must run on a device the spec's peaks
    describe."""
    import jax
    kind = jax.devices()[0].device_kind
    if not interpret and kind not in hw.device_kinds:
        raise ValueError(
            f"device_kind {kind!r} is not one {hw.name} describes "
            f"{hw.device_kinds}: its peak FLOP/s and HBM bandwidth are "
            "unknown, so no measured fraction of them can be formed")


def _time_fn(fn, repeats: int) -> float:
    """True-median-of-``repeats`` wall time, after one warmup compile call
    (``times[len//2]`` alone is the *upper* middle for even counts — a
    biased pick; ``statistics.median`` averages the two middles)."""
    if repeats < 1:
        raise ValueError(
            f"repeats must be >= 1 to measure anything, got {repeats}")
    import jax
    jax.block_until_ready(fn())          # compile + warm caches
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def _prefill_case(rng, dtype, seq: int, heads: int, head_dim: int,
                  interpret: bool):
    """Pure chunked-prefill workload over the real Pallas kernel:
    (timed fn, useful flops, hot bytes). One full-chunk causal attention
    over the cache; flops = causal QK^T + PV = 4 · Hq · D · Sq · Skv / 2,
    bytes = q/k/v read + output write."""
    import jax.numpy as jnp

    from repro.kernels.chunked_prefill import chunked_prefill_attention

    q = jnp.asarray(rng.normal(size=(1, seq, heads, head_dim)), dtype)
    kc = jnp.asarray(rng.normal(size=(1, seq, heads, head_dim)), dtype)
    vc = jnp.asarray(rng.normal(size=(1, seq, heads, head_dim)), dtype)
    starts = jnp.zeros((1,), jnp.int32)
    flops = 4.0 * heads * head_dim * seq * seq / 2.0
    nbytes = 4.0 * seq * heads * head_dim * jnp.dtype(dtype).itemsize
    return (lambda: chunked_prefill_attention(q, kc, vc, starts,
                                              interpret=interpret),
            flops, nbytes)


def _decode_case(rng, dtype, batch: int, heads: int, head_dim: int,
                 page_size: int, pages_per_seq: int, interpret: bool):
    """Pure paged-decode workload over a block-table-indirected pool:
    (timed fn, useful flops, hot bytes). Decode streams every attended
    K/V byte once — the memory roofline side."""
    import jax.numpy as jnp

    from repro.kernels.paged_attention import paged_attention

    n_pages = batch * pages_per_seq + 1
    ctx = page_size * pages_per_seq
    qd = jnp.asarray(rng.normal(size=(batch, heads, head_dim)), dtype)
    kp = jnp.asarray(
        rng.normal(size=(n_pages, page_size, heads, head_dim)), dtype)
    vp = jnp.asarray(
        rng.normal(size=(n_pages, page_size, heads, head_dim)), dtype)
    bt = jnp.asarray(rng.permutation(n_pages)[: batch * pages_per_seq]
                     .reshape(batch, pages_per_seq), jnp.int32)
    lengths = jnp.full((batch,), ctx, jnp.int32)
    flops = 4.0 * batch * heads * head_dim * ctx
    nbytes = 2.0 * batch * ctx * heads * head_dim * jnp.dtype(dtype).itemsize
    return (lambda: paged_attention(qd, kp, vp, bt, lengths,
                                    interpret=interpret),
            flops, nbytes)


def _gemm_case(rng, dtype, seq: int, batch: int):
    """Full-layer GEMM workload over the serving executor's own batched,
    donation-aware entry points (``ExecutorKernels.prefill_fn`` /
    ``decode_fn``): a tiny 2-layer dense model driven through the exact
    slot-indexed jitted functions ``RealExecutor`` runs, so the measured
    fraction prices the serving path — slot gather/scatter, bucket
    padding and on-device sampling included — not a bespoke harness.
    Returns ``(prefill_fn, prefill_flops, decode_fn, decode_flops)`` with
    the canonical 2 · n_active flops/token accounting the cost model
    uses, so the measured fraction is an apples-to-apples MFU."""
    import jax
    import jax.numpy as jnp

    from repro.models.api import build
    from repro.models.layers import ModelConfig
    from repro.perf.model import build_cost_spec
    from repro.serving.executor import ExecutorKernels

    cfg = ModelConfig(name="calib-gemm", family="dense", num_layers=2,
                      d_model=128, num_heads=2, num_kv_heads=2, head_dim=64,
                      d_ff=512, vocab_size=512)
    api = build(cfg)
    params = api.init(jax.random.PRNGKey(0))
    if dtype is not None:
        params = jax.tree.map(
            lambda a: a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating)
            else a, params)
    n_active = build_cost_spec(cfg).n_active
    # +1 cache row so the decode write at position ``seq`` stays in bounds
    kernels = ExecutorKernels(api, max_slots=batch, max_len=seq + 1)
    state = {"cache": api.init_cache(batch, seq + 1)}
    bucket = kernels.bucket_for(seq)
    tokens = rng.integers(0, cfg.vocab_size, size=(batch, seq))
    chunk = jnp.zeros((batch, bucket), jnp.int32).at[:, :seq].set(
        jnp.asarray(tokens, jnp.int32))
    slots = jnp.arange(batch, dtype=jnp.int32)
    starts = jnp.zeros((batch,), jnp.int32)
    takes = jnp.full((batch,), seq, jnp.int32)
    pfn = kernels.prefill_fn(bucket, batch)

    def prefill_call():
        # thread the cache: donate_argnums consumes the argument buffer
        toks, _, state["cache"] = pfn(params, state["cache"], chunk, slots,
                                      starts, takes)
        return toks

    lengths = jnp.full((batch,), seq, jnp.int32)
    step = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(batch,)),
                       jnp.int32)

    def decode_call():
        toks, state["cache"] = kernels.decode_fn(params, state["cache"],
                                                 step, lengths)
        return toks

    jax.block_until_ready(prefill_call())    # decode times a filled cache
    return (prefill_call, 2.0 * n_active * batch * seq,
            decode_call, 2.0 * n_active * batch)


def calibrate_hardware(hw: HardwareSpec = V5E, *,
                       seq: int = 256, heads: int = 4, head_dim: int = 64,
                       batch: int = 4, page_size: int = 16,
                       pages_per_seq: int = 8, repeats: int = 3,
                       interpret: Optional[bool] = None,
                       gemm: bool = True,
                       ) -> tuple[HardwareSpec, KernelCalibration]:
    """Measure achieved MFU / bandwidth-efficiency of the real serving
    kernels and return ``hw`` with the measured constants substituted.

    With ``gemm=True`` (default) the attention microkernel timings are
    blended with a full-layer dense-forward GEMM pass, so the returned
    MFU reflects the MLP-dominated regime a serving iteration actually
    spends most of its flops in:

        mfu = (attn_flops + gemm_flops) / ((t_attn + t_gemm) · peak)

    Shapes default small enough that interpret-mode (non-TPU) calibration
    finishes in seconds; on a TPU pass serving-sized shapes
    (seq=2048, head_dim=128, page_size=64) for representative numbers.
    Compiled (``interpret=False``), the device must be one ``hw``
    describes (``HardwareSpec.device_kinds``); otherwise ValueError."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    device = jax.default_backend()
    if interpret is None:
        interpret = device != "tpu"
    _check_device(hw, interpret)
    rng = np.random.default_rng(0)
    dtype = jnp.float32 if interpret else jnp.bfloat16

    prefill_fn, p_flops, _ = _prefill_case(rng, dtype, seq, heads, head_dim,
                                           interpret)
    t_p = _time_fn(prefill_fn, repeats)
    mfu_p = _frac(p_flops / (t_p * hw.peak_flops), interpret, "mfu_prefill")

    decode_fn, d_flops, d_bytes = _decode_case(
        rng, dtype, batch, heads, head_dim, page_size, pages_per_seq,
        interpret)
    t_d = _time_fn(decode_fn, repeats)
    mfu_d = _frac(d_flops / (t_d * hw.peak_flops), interpret, "mfu_decode")
    bw_eff = _frac(d_bytes / (t_d * hw.hbm_bw), interpret, "bw_eff")

    gp_t = gd_t = gp_f = gd_f = mfu_gp = mfu_gd = 0.0
    if gemm:
        gp_fn, gp_f, gd_fn, gd_f = _gemm_case(rng, dtype, seq, batch)
        gp_t = _time_fn(gp_fn, repeats)
        gd_t = _time_fn(gd_fn, repeats)
        mfu_gp = _frac(gp_f / (gp_t * hw.peak_flops), interpret,
                       "mfu_gemm_prefill")
        mfu_gd = _frac(gd_f / (gd_t * hw.peak_flops), interpret,
                       "mfu_gemm_decode")
        # blended phase MFU: one combined workload, one combined clock
        mfu_p = _frac((p_flops + gp_f) / ((t_p + gp_t) * hw.peak_flops),
                      interpret, "mfu_prefill")
        mfu_d = _frac((d_flops + gd_f) / ((t_d + gd_t) * hw.peak_flops),
                      interpret, "mfu_decode")

    cal = KernelCalibration(
        mfu_prefill=mfu_p, mfu_decode=mfu_d, bw_eff=bw_eff,
        prefill_seconds=t_p, decode_seconds=t_d,
        prefill_flops=p_flops, decode_flops=d_flops, decode_bytes=d_bytes,
        device=device,
        gemm_prefill_seconds=gp_t, gemm_decode_seconds=gd_t,
        gemm_prefill_flops=gp_f, gemm_decode_flops=gd_f,
        mfu_gemm_prefill=mfu_gp, mfu_gemm_decode=mfu_gd)
    measured = dataclasses.replace(
        hw, name=f"{hw.name}-measured",
        mfu_prefill=mfu_p, mfu_decode=mfu_d, bw_eff=bw_eff)
    return measured, cal


@dataclasses.dataclass(frozen=True)
class InterferenceCalibration:
    """What the mixed-vs-pure grid sweep measured, per cell."""
    table: InterferenceTable
    decode_batches: tuple           # grid axis values (= table edges)
    chunk_sizes: tuple
    pure_prefill_s: tuple           # per chunk size
    pure_decode_s: tuple            # per decode batch
    mixed_s: tuple                  # row-per-batch grid of mixed times
    device: str


def calibrate_interference(hw: HardwareSpec = V5E, *,
                           decode_batches: tuple = (1, 4, 8),
                           chunk_sizes: tuple = (128, 256),
                           heads: int = 4, head_dim: int = 64,
                           page_size: int = 16, pages_per_seq: int = 8,
                           repeats: int = 3,
                           interpret: Optional[bool] = None,
                           gamma_max: float = 1.0,
                           ) -> tuple[InterferenceTable,
                                      InterferenceCalibration]:
    """Measure the §IV mixed-batch contention coefficient γ per
    (decode-batch, chunk-size) bucket from the repo's own serving kernels.

    For every grid cell the real Pallas kernels run *pure* (the
    chunked-prefill attention alone, the paged decode attention alone)
    and *mixed* (both in one composed call — how a multiplexing worker's
    iteration actually executes), and the cell's measured excess over the
    perfect-overlap floor ``max(t_prefill, t_decode)`` solves the cost
    model's penalty form for γ::

        t_mixed = max(t_p, t_d) + γ · β_p · β_d · min(t_p, t_d)

    with β from the kernels' flop/byte rooflines — the same *functional
    form* as ``CostModel._interference``, evaluated over the attention
    kernels' own operands. γ is therefore a dimensionless contention
    coefficient measured on the attention path; the model applies it to
    its full-phase unit (GEMMs + weight streaming included), treating
    attention-path contention as representative of the whole phase's —
    the approximation the ROADMAP's on-TPU validation item exists to
    check. γ is clamped into [0, ``gamma_max``] — ``gamma_max=1`` keeps
    the model's guarantee that a mixed iteration never exceeds the
    fully-serialised sum. Off-TPU
    (interpret-mode Pallas) the two kernels cannot overlap at all, so γ
    rails toward that serialised ceiling — still well-defined, and the
    same harness on a real TPU lands wherever the hardware actually sits
    between perfect overlap and serialisation.

    Returns the bucketed table (edges = the swept grid values as bucket
    lower bounds) plus the raw per-cell measurements."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    if not decode_batches or not chunk_sizes:
        raise ValueError("calibrate_interference needs a non-empty grid")
    decode_batches = tuple(sorted(decode_batches))
    chunk_sizes = tuple(sorted(chunk_sizes))
    device = jax.default_backend()
    if interpret is None:
        interpret = device != "tpu"
    _check_device(hw, interpret)
    rng = np.random.default_rng(0)
    dtype = jnp.float32 if interpret else jnp.bfloat16
    peak_c = hw.peak_flops
    mem = hw.hbm_bw * hw.bw_eff

    # one workload per axis value, shared by the pure timing and every
    # mixed cell it appears in — the mixed run times the SAME operands
    # its pure baseline did. Alone-times are per-axis (a chunk's does not
    # depend on which decode batch it will be mixed with); mixed per cell.
    pre = {c: _prefill_case(rng, dtype, c, heads, head_dim, interpret)
           for c in chunk_sizes}
    dec = {b: _decode_case(rng, dtype, b, heads, head_dim, page_size,
                           pages_per_seq, interpret)
           for b in decode_batches}
    t_p = {c: _time_fn(pre[c][0], repeats) for c in chunk_sizes}
    t_d = {b: _time_fn(dec[b][0], repeats) for b in decode_batches}
    mixed_rows, gamma_rows = [], []
    for b in decode_batches:
        mixed_row, gamma_row = [], []
        for c in chunk_sizes:
            pf, p_flops, p_bytes = pre[c]
            df, d_flops, d_bytes = dec[b]
            t_mix = _time_fn(lambda: (pf(), df()), repeats)
            # kernel-level flop/byte accounting -> phase boundedness
            t_cp = p_flops / (peak_c * hw.mfu_prefill)
            t_mp = p_bytes / mem
            t_cd = d_flops / (peak_c * hw.mfu_decode)
            t_md = d_bytes / mem
            beta_p = t_cp / max(t_cp, t_mp)
            beta_d = t_md / max(t_cd, t_md)
            unit = beta_p * beta_d * min(t_p[c], t_d[b])
            excess = t_mix - max(t_p[c], t_d[b])
            gamma = min(max(excess / unit, 0.0), gamma_max) \
                if unit > 1e-12 else 0.0
            mixed_row.append(t_mix)
            gamma_row.append(gamma)
        mixed_rows.append(tuple(mixed_row))
        gamma_rows.append(tuple(gamma_row))

    table = InterferenceTable(decode_edges=decode_batches,
                              chunk_edges=chunk_sizes,
                              gamma=tuple(gamma_rows))
    cal = InterferenceCalibration(
        table=table, decode_batches=decode_batches, chunk_sizes=chunk_sizes,
        pure_prefill_s=tuple(t_p[c] for c in chunk_sizes),
        pure_decode_s=tuple(t_d[b] for b in decode_batches),
        mixed_s=tuple(mixed_rows), device=device)
    return table, cal


class CalibratedRooflineBackend:
    """ExecutionBackend whose clock is a roofline instantiated from
    measured kernel efficiency instead of the assumed constants (the
    ROADMAP's "batched roofline with measured MFU" backend).

    Runs the calibration once at construction; ``run_iteration`` then
    prices every composed iteration with the measured model. The
    per-worker cost models the engine carries (admission, capacity) are
    untouched — only the *clock* comes from measurements, which is the
    honest split: capacity is a spec property, speed is an empirical one."""

    def __init__(self, cfg, worker: WorkerSpec = WorkerSpec(),
                 page_size: int = 16, interpret: Optional[bool] = None,
                 measure_interference: bool = False,
                 interference_kw: Optional[dict] = None,
                 **calibrate_kw):
        hw, self.calibration = calibrate_hardware(
            worker.hw, interpret=interpret, **calibrate_kw)
        self.interference_calibration = None
        if measure_interference:
            # solve γ against the MEASURED spec — the same constants the
            # model will recompute β with when applying the penalty
            table, self.interference_calibration = calibrate_interference(
                hw, interpret=interpret, **(interference_kw or {}))
            hw = dataclasses.replace(hw, interference=table)
        self.cost = CostModel(cfg, dataclasses.replace(worker, hw=hw),
                              page_size=page_size)

    def run_iteration(self, worker, plan) -> float:
        return self.cost.iteration_time(
            plan.n_decode, plan.sum_ctx, plan.prefill_tokens,
            plan.prefill_ctx_offset)

    def on_finish(self, req) -> None:
        pass

    def on_migrate(self, req, src_wid: int, dst_wid: int) -> None:
        pass
