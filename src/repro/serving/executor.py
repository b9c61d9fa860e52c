"""Executors: the clock sources behind the engine.

* ``SimExecutor`` — the analytical cost model (default for benchmarks).
* ``RealExecutor`` — actually runs the JAX model on this host: slot-based
  batched cache, chunked prefill into per-slot cache views, batched decode
  across slots. Iteration durations are measured wall-clock. This proves
  the scheduler drives a real model end-to-end (examples + integration
  tests use smoke-scale configs).
* ``RealJaxBackend`` — the ``ExecutionBackend`` adapter that plugs a
  ``ClusterRealExecutors`` registry into the unified ``ClusterScheduler``:
  real compute + wall-clock durations (``clock="wall"``), or real compute
  under the cost-model clock (``clock="model"``) so scheduling decisions
  are bit-identical to the pure simulator — the backend-parity guarantee.

Two execution regimes per executor:

* ``batched=False`` — the scalar reference: one unjitted prefill-chunk
  call per request (a compile per distinct chunk length), a full-cache
  gather/scatter copy per chunk, and a host sync per sampled token.
  Kept bit-for-bit as the seed path; the parity tests compare against it.
* ``batched=True`` (default) — the fast path: prefill chunks are padded
  to a pow2 **bucket grid** and all same-bucket parts of an iteration run
  as ONE jitted slot-indexed call that updates the slotted cache in place
  (``jax.lax.dynamic_slice`` row gather + ``jax.lax.dynamic_update_slice``
  row scatter under ``donate_argnums``), then the decode batch, which
  writes its one new row per slot and layer into the donated cache in
  place (the model carries the stacked cache through its layer scan) and
  attends over the decoding slots' live positions only, with
  exactly one ``block_until_ready`` and one device->host token transfer
  per iteration. Bucket padding never writes past the cache: rows pad
  LEFT by re-feeding already-prefilled prefix tokens (recomputing the
  same KV) and only spill right while ``start + bucket <= max_len``;
  batch rows pad by duplicating row 0, so writes stay idempotent and the
  cache geometry is identical to the scalar path. Compiled entry points
  live in a per-cluster ``ExecutorKernels`` (identical shapes across
  replicas => one compile per (bucket, rows) for the whole cluster),
  warmed over the bucket grid at construction so first-iteration compile
  latency never poisons ``OnlinePredictor`` EWMAs.

The fast path requires the chunked-prefill contract and a uniform slotted
{"k","v"} cache (dense/moe/vlm transformers); ring-cache and stateful
families (gemma2 sliding window, rwkv, zamba2, whisper) transparently
fall back to the scalar reference even under ``batched=True``.
"""
from __future__ import annotations

import collections
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro import obs
from repro.kernels import decode_attention
from repro.models import api as model_api
from repro.models.transformer import layer_windows_py
from repro.perf import CostModel
from repro.sched.backend import SlotExhausted
from repro.serving.engine import IterationPlan, Worker

_BUCKET_FLOOR = 32


def _stamp() -> float:
    return time.perf_counter()  # lint: allow-wallclock(the real executor times its phases on the host clock)


def _bucket_grid(max_len: int) -> tuple[int, ...]:
    """Pow2 chunk buckets from the floor up, capped by the cache length
    (the last bucket is ``max_len`` itself so padded writes stay in
    bounds even for a non-pow2 cache)."""
    grid = []
    b = min(_BUCKET_FLOOR, max_len)
    while b < max_len:
        grid.append(b)
        b *= 2
    grid.append(max_len)
    return tuple(grid)


def _next_pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length() if n > 1 else 1


def _uniform_cache(cache) -> bool:
    """True for the slotted dict-of-arrays cache the bucketed kernels can
    row-index: {"k","v"} with head-major (L, B, H, S, D) leaves, the slot
    on axis 1. Ring caches (tuple
    leaves) and stateful pytrees fall back to the scalar path."""
    return (isinstance(cache, dict) and set(cache.keys()) == {"k", "v"}
            and all(getattr(a, "ndim", 0) == 5 for a in cache.values()))


def _slice_row(tree, idx):
    """One slot row (axis 1) of every leaf, via ``lax.dynamic_slice`` so a
    traced index is allowed."""
    return jax.tree.map(
        lambda a: lax.dynamic_slice(
            a, (0, idx) + (0,) * (a.ndim - 2),
            (a.shape[0], 1) + a.shape[2:]), tree)


def _put_row(tree, row, idx):
    """Write a one-slot ``row`` (from ``_slice_row``) at slot ``idx``."""
    return jax.tree.map(
        lambda a, r: lax.dynamic_update_slice(
            a, r.astype(a.dtype), (0, idx) + (0,) * (a.ndim - 2)),
        tree, row)


# migration: slice a slot row on the source device, write it on the
# destination's (the cache argument is donated: updated in place)
_take_row = jax.jit(_slice_row)
_give_row = jax.jit(_put_row, donate_argnums=0)


def _on_device(device, make):
    """``make()``'s arrays, created on and committed to ``device``."""
    with jax.default_device(device):
        return jax.device_put(make(), device)


class ExecutorKernels:
    """Jitted, slot-indexed entry points over one cache geometry, shared
    by every executor in a cluster (replicas have identical shapes, so
    each (bucket, rows) signature compiles exactly once per process).

    ``prefill_traces`` / ``decode_traces`` increment only when jax
    actually traces an entry point — the compile-count regression tests
    pin them to the bucket grid, not to the number of distinct chunk
    lengths seen. Executors on different devices share the traces; each
    device compiles its own executable.
    """

    def __init__(self, api, max_slots: int, max_len: int):
        self.api = api
        self.max_slots = max_slots
        self.max_len = max_len
        self.buckets = _bucket_grid(max_len)
        self.prefill_traces = 0
        self.decode_traces = 0
        self._prefill_fns: dict[tuple[int, int], object] = {}
        self._decode = None

    def bucket_for(self, take: int) -> int:
        for b in self.buckets:
            if take <= b:
                return b
        raise ValueError(f"chunk of {take} tokens exceeds max_len "
                         f"{self.max_len}")

    # ---------------------------------------------------------- entry points
    def prefill_fn(self, bucket: int, rows: int):
        """Batched bucketed prefill: gathers ``rows`` slot views, runs one
        padded ``prefill_chunk``, scatters the rows back in place, and
        samples every row's next token on device. Returns ``(tokens,
        last-token logits, cache)``; the executor reads only the tokens."""
        key = (bucket, rows)
        fn = self._prefill_fns.get(key)
        if fn is None:
            api = self.api

            def prefill_step(params, cache, chunk, slots, starts, takes):
                self.prefill_traces += 1     # trace-time only: a jit miss
                view = jax.tree.map(
                    lambda *xs: jnp.concatenate(xs, axis=1),
                    *[_slice_row(cache, slots[i]) for i in range(rows)])
                logits, view = api.prefill_chunk(
                    params, view, chunk, starts, take=takes)
                for i in range(rows):
                    cache = _put_row(cache, _slice_row(view, i), slots[i])
                toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return toks, logits, cache

            fn = jax.jit(prefill_step, donate_argnums=1)
            self._prefill_fns[key] = fn
        return fn

    @property
    def decode_fn(self):
        if self._decode is None:
            api = self.api

            def decode_step(params, cache, tokens, lengths):
                self.decode_traces += 1
                logits, cache = api.decode(params, cache, tokens, lengths)
                return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

            self._decode = jax.jit(decode_step, donate_argnums=1)
        return self._decode

    # --------------------------------------------------------------- warmup
    def warmup(self, params, device) -> None:
        """Compile the (bucket, 1-row) grid + the decode step up front on a
        throwaway cache on ``device`` (where ``params`` live), so the first
        scheduled iterations measure steady-state execution (not
        compilation) — the durations that feed the OnlinePredictor EWMAs."""
        cache = _on_device(device, lambda: self.api.init_cache(
            self.max_slots, self.max_len))
        one = jnp.zeros((1,), jnp.int32)
        for b in self.buckets:
            _, _, cache = self.prefill_fn(b, 1)(
                params, cache, jnp.zeros((1, b), jnp.int32), one, one,
                jnp.ones((1,), jnp.int32))
        zeros = jnp.zeros((self.max_slots,), jnp.int32)
        _, cache = self.decode_fn(params, cache, zeros, zeros)
        jax.block_until_ready(cache)


class SimExecutor:
    def __init__(self, cost: CostModel):
        self.cost = cost

    def duration_fn(self):
        return lambda worker, plan: worker.plan_duration(plan)


class RealExecutor:
    """One executor per worker; owns params + a slotted cache, both on
    ``device`` (default: JAX's first device)."""

    def __init__(self, cfg, rng, max_slots: int = 8, max_len: int = 256,
                 params=None, batched: bool = True, wid: int = 0,
                 kernels: Optional[ExecutorKernels] = None, owner=None,
                 device=None):
        self.cfg = cfg
        self.api = model_api.build(cfg)
        self.params = params if params is not None else self.api.init(rng)
        self.max_slots = max_slots
        self.max_len = max_len
        self.wid = wid
        self.device = device if device is not None else jax.devices()[0]
        self.cache = _on_device(
            self.device, lambda: self.api.init_cache(max_slots, max_len))
        self.free_slots = list(range(max_slots))
        self.slot_of: dict[int, int] = {}
        self.owner = owner                   # cluster rid -> wid registry
        self.lengths = np.zeros(max_slots, np.int32)
        self.prompts: dict[int, np.ndarray] = {}     # rid -> prompt tokens
        self.generated: dict[int, list[int]] = {}
        self._decode_fn = jax.jit(
            lambda p, c, t, l: self.api.decode(p, c, t, l))
        self.batched = batched
        self.fast = bool(batched and self.api.prefill_chunk is not None
                         and _uniform_cache(self.cache))
        if self.fast and kernels is None:
            kernels = ExecutorKernels(self.api, max_slots, max_len)
        self.kernels = kernels
        if self.fast:
            # what the decode attention kernel is asked to read: blocks of
            # ``_kv_block`` positions (K and V: ``_kv_block_bytes``), per
            # layer window (layers that share a window read alike), out of
            # the whole cache's ``_kv_cache_bytes``
            k = self.cache["k"]
            _, _, hkv, _, d = k.shape
            self._kv_block = decode_attention.block_size(
                max_len, hkv * d * k.dtype.itemsize)
            self._kv_block_bytes = 2 * hkv * self._kv_block * d * \
                k.dtype.itemsize
            self._kv_cache_bytes = sum(a.nbytes for a in self.cache.values())
            self._kv_windows = collections.Counter(layer_windows_py(cfg))

    # ------------------------------------------------------------ requests
    def register(self, req) -> None:
        if req.rid not in self.prompts:
            rng = np.random.default_rng(req.rid)
            self.prompts[req.rid] = rng.integers(
                0, self.cfg.vocab_size, size=req.prompt_len).astype(np.int32)
            self.generated[req.rid] = []

    def _slot(self, rid: int) -> int:
        if rid not in self.slot_of:
            if not self.free_slots:
                raise SlotExhausted(self.wid, rid, self.max_slots)
            self.slot_of[rid] = self.free_slots.pop()
            self.lengths[self.slot_of[rid]] = 0
            if self.owner is not None:
                self.owner[rid] = self.wid
        return self.slot_of[rid]

    def release(self, rid: int) -> None:
        slot = self.slot_of.pop(rid, None)
        if slot is not None:
            self.lengths[slot] = 0
            self.free_slots.append(slot)
            if self.owner is not None and self.owner.get(rid) == self.wid:
                del self.owner[rid]

    # ----------------------------------------------------------- execution
    def _cache_view(self, slot: int):
        return jax.tree.map(lambda a: a[:, slot:slot + 1], self.cache)

    def _cache_write(self, slot: int, view) -> None:
        self.cache = jax.tree.map(
            lambda a, s: a.at[:, slot:slot + 1].set(s), self.cache, view)

    def run_prefill_chunk(self, req, tokens_this_chunk: int) -> None:
        self.register(req)
        slot = self._slot(req.rid)
        start = int(req.prefilled_tokens)
        take = tokens_this_chunk
        chunk = self.prompts[req.rid][start:start + take]
        chunk_j = jnp.asarray(chunk[None, :], jnp.int32)
        starts = jnp.asarray([start], jnp.int32)
        view = self._cache_view(slot)
        if self.api.prefill_chunk is not None:
            logits, view = self.api.prefill_chunk(
                self.params, view, chunk_j, starts)
        else:
            # stateful families: re-run full prefill up to this point
            full = self.prompts[req.rid][: start + take]
            view = self._fresh_view()
            logits, view = self.api.prefill(
                self.params, view, jnp.asarray(full[None, :], jnp.int32),
                jnp.asarray([start + take], jnp.int32))
        self._cache_write(slot, view)
        self.lengths[slot] = start + take
        if start + take >= req.prompt_len:
            tok = int(jnp.argmax(logits[0]))
            self.generated[req.rid].append(tok)

    def _fresh_view(self):
        one = self.api.init_cache(1, self.max_len)
        return one

    def run_decode_batch(self, reqs) -> None:  # lint: not-parity(the decode batch IS the unit of work; run_plan's scalar regime calls this directly)
        if not reqs:
            return
        slots = [self._slot(r.rid) for r in reqs]
        tokens = np.zeros(self.max_slots, np.int32)
        lengths = np.array(self.lengths)
        for r, s in zip(reqs, slots):
            tokens[s] = self.generated[r.rid][-1]
        logits, self.cache = self._decode_fn(
            self.params, self.cache, jnp.asarray(tokens),
            jnp.asarray(lengths))
        logits = np.asarray(logits)
        for r, s in zip(reqs, slots):
            self.generated[r.rid].append(int(logits[s].argmax()))
            self.lengths[s] += 1

    # ------------------------------------------------------ fused fast path
    def assign_slots(self, plan: IterationPlan) -> None:
        """Reserve every slot the plan needs BEFORE any compute runs, so a
        ``SlotExhausted`` refusal is side-effect-free on the device (re-
        running a final prefill chunk would double-append its sampled
        token)."""
        for req, _ in plan.prefill_parts:
            self.register(req)
            self._slot(req.rid)
        for r in plan.decode_reqs:
            self._slot(r.rid)

    def run_plan(self, plan: IterationPlan) -> float:
        """Execute one composed iteration (either regime), returning after
        the device is idle. Returns its measured duration, seconds: the sum
        of the phases it hands to ``obs.RECORDER`` (the scalar regime reads
        its tokens back inside its calls, so its readback phase is 0)."""
        if self.fast:
            return self._run_plan_fast(plan)
        t0 = _stamp()
        with obs.span("backend.prepare", wid=self.wid):
            self.assign_slots(plan)
        t1 = _stamp()
        with obs.span("backend.dispatch"):
            for req, take in plan.prefill_parts:
                self.run_prefill_chunk(req, take)
            self.run_decode_batch(plan.decode_reqs)
        t2 = _stamp()
        with obs.span("backend.sync"):
            jax.block_until_ready(self.cache)
        t3 = _stamp()
        return self._record(plan, t0, t1, t2, t3, t3)

    def _record(self, plan: IterationPlan, t0: float, t1: float, t2: float,
                t3: float, t4: float, kv_read: Optional[int] = None) -> float:
        obs.RECORDER.executor_phases(
            plan, obs.Phases(t1 - t0, t2 - t1, t3 - t2, t4 - t3), kv_read,
            None if kv_read is None else self._kv_cache_bytes)
        return t4 - t0

    def _decode_kv_bytes(self, lengths: np.ndarray) -> int:
        """K and V bytes of the live blocks the decode call's attention is
        asked to read for these ``lengths``, by the kernel's own block
        rule."""
        return self._kv_block_bytes * sum(
            n * int(decode_attention.live_blocks(
                lengths, w, self._kv_block)[1].sum())
            for w, n in self._kv_windows.items())

    def _prepare_calls(self, plan: IterationPlan) \
            -> tuple[list, list, Optional[int]]:
        """The iteration's calls, with their inputs on the device:
        ``[(bucket, rows, args) | (None, None, args)]`` (a prefill bucket
        group, or the decode batch); per call, how its tokens are read
        back; and the KV bytes of the live blocks the decode call's
        attention is asked to read (None without one). A slot that does not
        decode (free, or in prefill) gets length -1: the decode step reads
        nothing for it."""
        groups: dict[int, list] = {}
        for req, take in plan.prefill_parts:
            bucket = self.kernels.bucket_for(take)
            groups.setdefault(bucket, []).append((req, take))
        calls = []
        pending = []        # (rows-of-Optional[req] | decode pairs)
        for bucket in sorted(groups):
            parts = groups[bucket]
            rows = _next_pow2(len(parts))
            chunk = np.zeros((rows, bucket), np.int32)
            slots = np.zeros(rows, np.int32)
            starts = np.zeros(rows, np.int32)
            takes = np.ones(rows, np.int32)
            finals: list = [None] * rows
            for i, (req, take) in enumerate(parts):
                start = int(req.prefilled_tokens)
                slot = self.slot_of[req.rid]
                # pad LEFT with the already-prefilled prefix (recomputing
                # identical KV) so the padded write window never crosses
                # max_len — dynamic_update_slice clamps, which would slide
                # real KV rows to wrong positions
                pad_l = min(bucket - take, start)
                row_start = start - pad_l
                toks = self.prompts[req.rid][row_start:start + take]
                chunk[i, :len(toks)] = toks
                slots[i] = slot
                starts[i] = row_start
                takes[i] = pad_l + take
                self.lengths[slot] = start + take
                if start + take >= req.prompt_len:
                    finals[i] = req
            for i in range(len(parts), rows):   # duplicate row 0: idempotent
                chunk[i] = chunk[0]
                slots[i] = slots[0]
                starts[i] = starts[0]
                takes[i] = takes[0]
            calls.append((bucket, rows, (
                jnp.asarray(chunk), jnp.asarray(slots), jnp.asarray(starts),
                jnp.asarray(takes))))
            pending.append(("prefill", finals))
        kv_read = None
        if plan.decode_reqs:
            dpairs = [(r, self._slot(r.rid)) for r in plan.decode_reqs]
            tokens = np.zeros(self.max_slots, np.int32)
            lengths = np.full(self.max_slots, -1, np.int32)
            for r, s in dpairs:
                tokens[s] = self.generated[r.rid][-1]
                lengths[s] = self.lengths[s]
            calls.append((None, None, (jnp.asarray(tokens),
                                       jnp.asarray(lengths))))
            pending.append(("decode", dpairs))
            kv_read = self._decode_kv_bytes(lengths)
        return calls, pending, kv_read

    def _run_plan_fast(self, plan: IterationPlan) -> float:
        t0 = _stamp()
        with obs.span("backend.prepare", wid=self.wid):
            self.assign_slots(plan)
            calls, pending, kv_read = self._prepare_calls(plan)
        t1 = _stamp()
        tok_parts = []
        with obs.span("backend.dispatch"):
            for bucket, rows, args in calls:
                if bucket is None:
                    toks_dev, self.cache = self.kernels.decode_fn(
                        self.params, self.cache, *args)
                else:
                    toks_dev, _, self.cache = self.kernels.prefill_fn(
                        bucket, rows)(self.params, self.cache, *args)
                tok_parts.append(toks_dev)
        t2 = _stamp()
        # exactly one device sync + one device->host transfer per iteration
        with obs.span("backend.sync"):
            jax.block_until_ready(self.cache)
        t3 = _stamp()
        with obs.span("backend.readback"):
            if tok_parts:
                host = np.asarray(tok_parts[0]) if len(tok_parts) == 1 \
                    else np.asarray(jnp.concatenate(tok_parts))
            off = 0
            for kind, data in pending:
                if kind == "prefill":
                    for i, req in enumerate(data):
                        if req is not None:
                            self.generated[req.rid].append(
                                int(host[off + i]))
                    off += len(data)
                else:
                    for r, s in data:
                        self.generated[r.rid].append(int(host[off + s]))
                        self.lengths[s] += 1
                    off += self.max_slots
        return self._record(plan, t0, t1, t2, t3, _stamp(), kv_read)

    def duration_fn(self):
        """Measured-wall-clock duration_fn for the Simulator."""
        return lambda worker, plan: self.run_plan(plan)


class ClusterRealExecutors:
    """Per-worker RealExecutor registry + shared duration_fn dispatch.

    Worker ``i`` runs on ``devices[i % len(devices)]`` (default: every
    device JAX sees, so one worker per chip on a multi-chip host). All
    replicas share weights — one copy per device — AND compiled entry
    points (identical cache geometry => one jit cache for the cluster),
    warmed over the bucket grid on each device at construction.
    """

    def __init__(self, cfg, n_workers: int, rng=None, max_slots=8,
                 max_len=256, batched: bool = True, warmup: bool = True,
                 devices: Optional[Sequence] = None):
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        api = model_api.build(cfg)
        devices = list(devices) if devices is not None else jax.devices()
        placed = [devices[i % len(devices)] for i in range(n_workers)]
        params = jax.jit(api.init)(rng)      # replicas share weights
        on_dev = {d: jax.device_put(params, d) for d in dict.fromkeys(placed)}
        self.batched = batched
        self._owner: dict[int, int] = {}     # rid -> owning wid
        self.migrations: list[tuple[int, int, int]] = []  # (rid, src, dst)
        kernels = None
        if batched and api.prefill_chunk is not None and \
                _uniform_cache(api.init_cache(1, max_len)):
            kernels = ExecutorKernels(api, max_slots, max_len)
        self.kernels = kernels
        self.execs = {
            i: RealExecutor(cfg, rng, max_slots, max_len,
                            params=on_dev[placed[i]], batched=batched, wid=i,
                            kernels=kernels, owner=self._owner,
                            device=placed[i])
            for i in range(n_workers)
        }
        if kernels is not None and warmup:
            for d, p in on_dev.items():
                kernels.warmup(p, d)

    def duration_fn(self):
        def run(worker: Worker, plan: IterationPlan) -> float:
            return self.execs[worker.wid].duration_fn()(worker, plan)
        return run

    def on_finish(self, req) -> None:
        wid = self._owner.get(req.rid)
        if wid is not None:
            self.execs[wid].release(req.rid)

    def as_backend(self, clock: str = "wall") -> "RealJaxBackend":
        return RealJaxBackend(self, clock=clock)

    def migrate(self, req, src: int, dst: int) -> None:
        """Move the request across workers. Cache-true families move the
        KV slot row: sliced on the source device, ``device_put`` to the
        destination's (across chips, the ICI transfer; a no-op when both
        workers share a chip), written in place there. Stateful/ring
        families re-derive it by replaying prefill."""
        se, de = self.execs[src], self.execs[dst]
        de.prompts[req.rid] = se.prompts[req.rid]
        de.generated[req.rid] = list(se.generated[req.rid])
        slot = de._slot(req.rid)      # SlotExhausted surfaces to scheduler
        self.migrations.append((req.rid, src, dst))
        sslot = se.slot_of.get(req.rid)
        if de.fast and sslot is not None and se.fast:
            row = jax.device_put(_take_row(se.cache, jnp.int32(sslot)),
                                 de.device)
            de.cache = _give_row(de.cache, row, jnp.int32(slot))
            de.lengths[slot] = se.lengths[sslot]
            se.release(req.rid)
            return
        # replay KV on the destination (simulating the transfer)
        full = np.concatenate([
            de.prompts[req.rid],
            np.asarray(de.generated[req.rid][:-1], np.int32)]) \
            if len(de.generated[req.rid]) > 1 else de.prompts[req.rid]
        view = de._fresh_view()
        _, view = de.api.prefill(
            de.params, view, jnp.asarray(full[None, :], jnp.int32),
            jnp.asarray([len(full)], jnp.int32))
        de._cache_write(slot, view)
        de.lengths[slot] = len(full)
        se.release(req.rid)


class RealJaxBackend:
    """ExecutionBackend over per-worker RealExecutors.

    ``clock="wall"``   — report measured wall-clock durations (the real
                         serving configuration; feeds OnlinePredictor with
                         genuine execution times).
    ``clock="model"``  — run the real compute but report the analytical
                         cost-model duration. Scheduling then sees exactly
                         the timings the pure simulator sees, which makes
                         decision logs comparable across backends.

    A worker out of KV slots raises ``SlotExhausted`` before any compute
    runs; the scheduler turns it into a dispatch refusal (the request
    re-queues) instead of a crash.
    """

    def __init__(self, execs: ClusterRealExecutors, clock: str = "wall"):
        if clock not in ("wall", "model"):
            raise ValueError(f"clock must be 'wall' or 'model', got {clock!r}")
        self.execs = execs
        self.clock = clock

    def run_iteration(self, worker: Worker, plan: IterationPlan) -> float:
        measured = self.execs.execs[worker.wid].run_plan(plan)
        return measured if self.clock == "wall" else worker.plan_duration(plan)

    def on_finish(self, req) -> None:
        self.execs.on_finish(req)

    def on_migrate(self, req, src_wid: int, dst_wid: int) -> None:
        self.execs.migrate(req, src_wid, dst_wid)
