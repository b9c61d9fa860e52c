"""Mixture-of-Experts with real expert parallelism.

Two execution paths share one set of parameters:

* ``apply_moe_reference`` — pure jnp dense dispatch (every token through every
  expert, masked). O(E) FLOPs waste; used as the correctness oracle, for
  smoke tests, and for the tiny real-executor serving path.
* ``apply_moe_ep`` — shard_map expert parallelism. Experts are sharded over
  ``ep_axes`` (usually the whole mesh: 1–2 experts per chip for the 1T-class
  models, which cannot fit any replicated layout). Tokens are routed with a
  static-capacity all_to_all per mesh axis (composition of per-axis
  all_to_alls == full-mesh token exchange), computed, and routed back.

Layout inside the EP path
-------------------------
send/recv buffers are (N, L_e, C, d): N = #devices in the EP group,
L_e = experts per device (E padded to a multiple of N), C = per
(destination-device, local-expert) slot capacity. Tokens beyond capacity are
dropped (gates renormalised over the surviving top-k — standard GShard-style
drop). Because the buffer is bucketed per *local expert*, the expert GEMM is
a single batched einsum with zero masking waste.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.models.layers import ModelConfig, _dense_init, _activate


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def physical_experts(cfg: ModelConfig) -> int:
    if cfg.expert_pad_to <= 0:
        return cfg.num_experts
    return math.ceil(cfg.num_experts / cfg.expert_pad_to) * cfg.expert_pad_to


def init_moe(rng, cfg: ModelConfig):
    k = jax.random.split(rng, 5)
    e, d, f = physical_experts(cfg), cfg.d_model, cfg.d_ff
    p = {
        "router": _dense_init(k[0], (d, cfg.num_experts), jnp.float32),
        "w_gate": _dense_init(k[1], (e, d, f), cfg.dtype),
        "w_up": _dense_init(k[2], (e, d, f), cfg.dtype),
        "w_down": _dense_init(k[3], (e, f, d), cfg.dtype, in_axis_size=f),
    }
    if cfg.num_shared_experts:
        ks = jax.random.split(k[4], 3)
        fs = cfg.d_ff * cfg.num_shared_experts
        p["shared"] = {
            "w_gate": _dense_init(ks[0], (d, fs), cfg.dtype),
            "w_up": _dense_init(ks[1], (d, fs), cfg.dtype),
            "w_down": _dense_init(ks[2], (fs, d), cfg.dtype, in_axis_size=fs),
        }
    return p


# ---------------------------------------------------------------------------
# routing (shared by both paths)
# ---------------------------------------------------------------------------


def route(router_w, x, top_k: int, num_experts_padded: int):
    """x: (T, d) -> (gates (T,k) f32, expert_ids (T,k) i32).

    Padding experts (id >= real E) receive -inf logits and are never picked.
    """
    logits = x.astype(jnp.float32) @ router_w  # (T, E)
    e_real = logits.shape[-1]
    if num_experts_padded > e_real:
        pad = jnp.full((x.shape[0], num_experts_padded - e_real), -jnp.inf, jnp.float32)
        logits = jnp.concatenate([logits, pad], axis=-1)
    gate_vals, ids = lax.top_k(logits, top_k)
    gates = jax.nn.softmax(gate_vals, axis=-1)
    return gates, ids


# ---------------------------------------------------------------------------
# reference path (oracle)
# ---------------------------------------------------------------------------


def apply_moe_reference(params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """x: (B, S, d). Dense dispatch — every token through every expert."""
    b, s, d = x.shape
    e_phys = params["w_gate"].shape[0]
    xt = x.reshape(b * s, d)
    gates, ids = route(params["router"], xt, cfg.top_k, e_phys)
    # (T, E) combine weights
    combine = jnp.zeros((xt.shape[0], e_phys), jnp.float32)
    combine = combine.at[jnp.arange(xt.shape[0])[:, None], ids].add(gates)
    g = _activate(jnp.einsum("td,edf->tef", xt, params["w_gate"]), cfg.mlp_activation)
    u = jnp.einsum("td,edf->tef", xt, params["w_up"])
    y = jnp.einsum("tef,efd->ted", g * u, params["w_down"])
    out = jnp.einsum("ted,te->td", y.astype(jnp.float32), combine)
    out = out.astype(x.dtype).reshape(b, s, d)
    return out + _shared_expert(params, x, cfg)


def _shared_expert(params, x, cfg: ModelConfig):
    if "shared" not in params:
        return jnp.zeros_like(x)
    sp = params["shared"]
    g = _activate(jnp.einsum("bsd,df->bsf", x, sp["w_gate"]), cfg.mlp_activation)
    u = jnp.einsum("bsd,df->bsf", x, sp["w_up"])
    return jnp.einsum("bsf,fd->bsd", g * u, sp["w_down"])


# ---------------------------------------------------------------------------
# EP path
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class EPInfo:
    mesh: Mesh
    ep_axes: tuple[str, ...]      # axes experts are sharded over (in order)
    batch_axes: tuple[str, ...]   # axes the batch dim is sharded over
    seq_split_axis: str = "model"  # axis used to split tokens for routing
    capacity_factor: float = 2.0
    capacity_floor: int = 4       # min slots per (dst, local-expert) pair;
                                  # decode-batch hillclimb lever (§Perf)
    ep_mode: str = "alltoall"     # alltoall | allgather (tiny-batch decode:
                                  # broadcast tokens, compute local experts
                                  # masked, psum — moves O(T·d) instead of
                                  # O(N·C·d) padded buffers; §Perf)
    fused_a2a: bool = False       # single all_to_all over the whole EP
                                  # group instead of one per mesh axis
                                  # (halves dispatch wire volume; §Perf)

    @property
    def n_devices(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.ep_axes]))

    @property
    def seq_split(self) -> int:
        return int(self.mesh.shape[self.seq_split_axis])


def ep_padded_experts(num_experts: int, n_devices: int) -> tuple[int, int]:
    l_e = max(1, math.ceil(num_experts / n_devices))
    return l_e * n_devices, l_e


def _multi_axis_all_to_all(buf: jax.Array, info: EPInfo) -> jax.Array:
    """buf: (N, ...) where N = prod(ep_axes sizes), laid out so that the
    linear destination index is ``axis_index(ep_axes)`` (row-major over
    ep_axes).

    Fast path: one fused all_to_all over the whole EP group (named-axis
    tuple) — each element crosses the wire once. Fallback composes one
    tiled all_to_all per mesh axis, which moves the full buffer once *per
    axis* (†measured 2x wire volume on the kimi train cell — §Perf)."""
    if info.fused_a2a:
        try:
            return lax.all_to_all(buf, info.ep_axes, split_axis=0,
                                  concat_axis=0, tiled=True)
        except (TypeError, ValueError):
            pass
    sizes = [int(info.mesh.shape[a]) for a in info.ep_axes]
    rest = buf.shape[1:]
    x = buf.reshape(*sizes, *rest)
    for i, a in enumerate(info.ep_axes):
        x = lax.all_to_all(x, a, split_axis=i, concat_axis=i, tiled=True)
    return x.reshape(buf.shape)


def _dispatch_indices(ids, gates, l_e: int, n_dev: int, capacity: int):
    """Flatten (T,k) routing into send-buffer slots.

    Returns (slot (T*k,) int32 in [0, n_dev*l_e*capacity] — == size means
    dropped; flat buffer layout is (dst_dev, local_expert, capacity)).
    """
    tk = ids.shape[0] * ids.shape[1]
    flat_e = ids.reshape(tk)                      # global (padded) expert id
    bucket = flat_e                               # == dst*l_e + local_e
    order = jnp.argsort(bucket)                   # stable
    sorted_b = bucket[order]
    # rank within bucket: index - first-occurrence-index of this bucket value
    first = jnp.searchsorted(sorted_b, sorted_b, side="left")
    rank_sorted = jnp.arange(tk, dtype=jnp.int32) - first.astype(jnp.int32)
    rank = jnp.zeros((tk,), jnp.int32).at[order].set(rank_sorted)
    dropped = rank >= capacity
    slot = jnp.where(dropped, n_dev * l_e * capacity, bucket * capacity + rank)
    return slot.astype(jnp.int32), dropped


def apply_moe_ep(params, x: jax.Array, cfg: ModelConfig, info: EPInfo) -> jax.Array:
    """x: (B, S, d) — batch sharded over info.batch_axes, replicated over
    'model'. Output has the same layout."""
    mesh = info.mesh
    bspec = P(info.batch_axes, None, None)
    espec = P(info.ep_axes)  # leading (expert) dim over the whole EP group

    moe_params = {
        "router": params["router"],
        "w_gate": params["w_gate"],
        "w_up": params["w_up"],
        "w_down": params["w_down"],
    }
    pspecs = {
        "router": P(None, None),
        "w_gate": espec,
        "w_up": espec,
        "w_down": espec,
    }

    body = _moe_ep_allgather_local if info.ep_mode == "allgather" \
        else _moe_ep_local
    fn = jax.shard_map(
        functools.partial(body, cfg=cfg, info=info),
        mesh=mesh,
        in_specs=(pspecs, bspec),
        out_specs=bspec,
        check_vma=False,
    )
    out = fn(moe_params, x)
    return out + _shared_expert(params, x, cfg)


def _moe_ep_allgather_local(p, x, *, cfg: ModelConfig, info: EPInfo):
    """Tiny-batch EP (decode): broadcast all tokens to every device,
    compute the local experts over all tokens with combine-weight masking,
    psum the contributions. Collective volume O(T·d) + O(T·d) — beats the
    all_to_all's O(N·C·d) padded buffers whenever T << N·C."""
    b_loc, s, d = x.shape
    n_dev = info.n_devices
    l_e = p["w_gate"].shape[0]
    e_pad = l_e * n_dev

    xt = x.reshape(b_loc * s, d)
    # gather every device's tokens (over the batch axes only — x is already
    # replicated over 'model'). Reversed order so the FIRST batch axis ends
    # up outermost, matching axis_index(batch_axes) row-major order.
    x_all = xt
    for a in reversed(info.batch_axes):
        x_all = lax.all_gather(x_all, a, axis=0, tiled=True)
    t_all = x_all.shape[0]

    gates, ids = route(p["router"], x_all, cfg.top_k, e_pad)    # (T, k)
    my_dev = lax.axis_index(info.ep_axes)
    # combine weight of each token for each LOCAL expert: (T, l_e)
    local_expert_ids = my_dev * l_e + jnp.arange(l_e)[None, :]   # (1, l_e)
    w = jnp.einsum(
        "tkl->tl",
        jnp.where(ids[:, :, None] == local_expert_ids[:, None, :],
                  gates[:, :, None], 0.0))

    h = jnp.broadcast_to(x_all[None], (l_e, t_all, d))
    g = _activate(jnp.einsum("etd,edf->etf", h, p["w_gate"]),
                  cfg.mlp_activation)
    u = jnp.einsum("etd,edf->etf", h, p["w_up"])
    y = jnp.einsum("etf,efd->etd", g * u, p["w_down"])          # (l_e, T, d)
    contrib = jnp.einsum("etd,te->td", y.astype(jnp.float32),
                         w.astype(jnp.float32))
    # sum expert contributions across the EP group
    out_all = lax.psum(contrib, info.ep_axes)                   # (T, d)
    # slice back this device's batch rows
    my_batch = lax.axis_index(info.batch_axes)
    t_loc = b_loc * s
    out = lax.dynamic_slice_in_dim(out_all, my_batch * t_loc, t_loc, axis=0)
    return out.astype(x.dtype).reshape(b_loc, s, d)


def _moe_ep_local(p, x, *, cfg: ModelConfig, info: EPInfo):
    """Per-device body. x: (B_loc, S, d) — identical copy on every member of
    the 'model' axis; each member routes a distinct 1/seq_split slice."""
    b_loc, s, d = x.shape
    n_dev = info.n_devices
    # experts-per-device from the actual shard_map slice: the physical
    # table is padded to a multiple of the EP group (expert_pad_to)
    l_e = p["w_gate"].shape[0]
    e_pad = l_e * n_dev
    assert e_pad >= cfg.num_experts, (
        f"padded expert table ({e_pad}) smaller than real experts "
        f"({cfg.num_experts}) — set ModelConfig.expert_pad_to for this mesh")
    sp = info.seq_split

    t_all = b_loc * s
    t_chunk = -(-t_all // sp)  # ceil
    xt = x.reshape(t_all, d)
    if t_chunk * sp != t_all:
        xt = jnp.pad(xt, ((0, t_chunk * sp - t_all), (0, 0)))
    m_idx = lax.axis_index(info.seq_split_axis)
    x_chunk = lax.dynamic_slice_in_dim(xt, m_idx * t_chunk, t_chunk, axis=0)

    gates, ids = route(p["router"], x_chunk, cfg.top_k, e_pad)  # (Tc,k)
    tk = t_chunk * cfg.top_k
    capacity = max(info.capacity_floor,
                   math.ceil(info.capacity_factor * tk / e_pad))

    slot, dropped = _dispatch_indices(ids, gates, l_e, n_dev, capacity)
    nslots = n_dev * l_e * capacity

    tok_idx = jnp.repeat(jnp.arange(t_chunk, dtype=jnp.int32), cfg.top_k)
    send = jnp.zeros((nslots, d), x.dtype).at[slot].set(
        x_chunk[tok_idx], mode="drop"
    )
    send = send.reshape(n_dev, l_e, capacity, d)
    recv = _multi_axis_all_to_all(send, info)          # (n_dev, l_e, C, d)

    # ---- local expert compute: (l_e, n_dev*C, d) batched GEMMs ----------
    h = recv.transpose(1, 0, 2, 3).reshape(l_e, n_dev * capacity, d)
    # local expert weights arrive sharded (l_e, d, f) per device
    wg, wu, wd = p["w_gate"], p["w_up"], p["w_down"]
    g = _activate(jnp.einsum("etd,edf->etf", h, wg), cfg.mlp_activation)
    u = jnp.einsum("etd,edf->etf", h, wu)
    y = jnp.einsum("etf,efd->etd", g * u, wd)          # (l_e, n_dev*C, d)

    back = y.reshape(l_e, n_dev, capacity, d).transpose(1, 0, 2, 3)
    ret = _multi_axis_all_to_all(back, info)           # (n_dev, l_e, C, d)
    ret = ret.reshape(nslots, d)

    # ---- combine: gather each assignment's output, weight by gate -------
    safe_slot = jnp.where(dropped, 0, slot)
    picked = ret[safe_slot].astype(jnp.float32)        # (T*k, d)
    w = jnp.where(dropped, 0.0, gates.reshape(tk))
    contrib = picked * w[:, None]
    out_chunk = jnp.zeros((t_chunk, d), jnp.float32).at[tok_idx].add(contrib)
    out_chunk = out_chunk.astype(x.dtype)

    # ---- reassemble the full token set (undo the model-axis seq split) --
    full = lax.all_gather(out_chunk, info.seq_split_axis, axis=0, tiled=True)
    return full[:t_all].reshape(b_loc, s, d)


def apply_moe(params, x, cfg: ModelConfig, ep: Optional[EPInfo] = None):
    if ep is None:
        return apply_moe_reference(params, x, cfg)
    return apply_moe_ep(params, x, cfg, ep)
