"""PartitionSpec rules: map (arch config, mesh) -> pytree of PartitionSpecs.

Baseline policy (hillclimb surface — see EXPERIMENTS.md §Perf):
  * vocab / d_ff dims        -> 'model' (Megatron TP)
  * attention heads          -> 'model' iff divisible, else replicated
  * MoE expert dim           -> the whole mesh (EP; 1T-class models cannot
                                fit any replicated expert layout)
  * batch                    -> ('pod','data') iff divisible, else the KV
                                cache sequence dim goes to 'data'
                                (long-context split-K decode)
  * mamba d_inner / heads    -> 'model' (head-aligned after proj split)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.models.layers import ModelConfig


@dataclasses.dataclass(frozen=True)
class MeshRules:
    mesh: Mesh
    batch_axes: tuple[str, ...]          # e.g. ('pod','data') or ('data',)
    model_axis: str = "model"
    ep_axes: tuple[str, ...] = ()        # expert-parallel axes (full mesh)

    @property
    def tp(self) -> int:
        return int(self.mesh.shape[self.model_axis])

    @property
    def n_batch(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.batch_axes]))


def make_rules(mesh: Mesh) -> MeshRules:
    names = mesh.axis_names
    batch = tuple(a for a in names if a != "model")
    return MeshRules(mesh=mesh, batch_axes=batch, ep_axes=tuple(names))


def _head_axis(rules: MeshRules, n_heads: int) -> Optional[str]:
    return rules.model_axis if n_heads % rules.tp == 0 else None


def _ff_axis(rules: MeshRules, d_ff: int) -> Optional[str]:
    return rules.model_axis if d_ff % rules.tp == 0 else None


def param_specs(cfg: ModelConfig, params_tree: Any, rules: MeshRules):
    """Walk the params pytree and assign PartitionSpecs by path."""
    m = rules.model_axis
    hq = _head_axis(rules, cfg.num_heads)
    hkv = _head_axis(rules, cfg.num_kv_heads)
    ff = _ff_axis(rules, cfg.d_ff)
    dm = rules.model_axis if cfg.d_model % rules.tp == 0 else None
    ep = rules.ep_axes

    def spec_for(path: tuple[str, ...], leaf) -> P:
        keys = [getattr(p, "key", getattr(p, "name", str(p))) for p in path]
        name = keys[-1]
        joined = "/".join(str(k) for k in keys)
        nd = leaf.ndim if hasattr(leaf, "ndim") else len(leaf.shape)

        # --- embeddings -------------------------------------------------
        if "embed" in keys and name in ("table", "unembed"):
            v = leaf.shape[0]
            return P(m if v % rules.tp == 0 else None, None)
        if name == "vision_proj":
            return P(None, None)
        # --- rwkv (before attention: tm/cm reuse the wk/wv names) -------
        if "tm" in keys:
            if name in ("wr", "wg", "wk", "wv"):
                return P(None, dm)
            if name == "wo":
                return P(dm, None)
            if name == "decay_w2":
                return P(None, dm)
            if name in ("ln_out_scale", "ln_out_bias", "decay_base"):
                return P(dm)
            if name == "bonus_u":
                return P(_head_axis(rules, leaf.shape[0]), None)
            return P(*([None] * nd))
        if "cm" in keys:
            if name == "wk":
                return P(None, _ff_axis(rules, leaf.shape[1]))
            if name == "wv":
                return P(_ff_axis(rules, leaf.shape[0]), None)
            return P(*([None] * nd))
        # --- MoE (before generic mlp rules; expert weights are 3D) ------
        if "moe" in keys:
            if name == "router":
                return P(None, None)
            if name in ("w_gate", "w_up", "w_down") and nd == 3 and "shared" not in keys:
                return P(ep, None, None)
            if name in ("w_gate", "w_up"):
                return P(None, ff)
            if name == "w_down":
                return P(ff, None)
        # --- attention ---------------------------------------------------
        if name == "wq":
            return P(None, hq, None)
        if name in ("wk", "wv"):
            return P(None, hkv, None)
        if name == "wo":
            return P(hq, None, None)
        if name == "bq":
            return P(hq, None)
        if name in ("bk", "bv"):
            return P(hkv, None)
        # --- dense MLP -----------------------------------------------------
        if name in ("w_gate", "w_up") and nd == 2:
            fdim = leaf.shape[1]
            return P(None, m if fdim % rules.tp == 0 else None)
        if name == "w_down" and nd == 2:
            fdim = leaf.shape[0]
            return P(m if fdim % rules.tp == 0 else None, None)
        # --- mamba2 --------------------------------------------------------
        if name in ("in_z", "in_x"):
            return P(None, m if leaf.shape[1] % rules.tp == 0 else None)
        if name == "in_dt":
            return P(None, m if leaf.shape[1] % rules.tp == 0 else None)
        if name in ("in_b", "in_c"):
            return P(None, None)
        if name == "conv_wx":
            return P(None, m if leaf.shape[1] % rules.tp == 0 else None)
        if name == "conv_bx":
            return P(m if leaf.shape[0] % rules.tp == 0 else None)
        if name in ("A_log", "dt_bias", "D"):
            return P(m if leaf.shape[0] % rules.tp == 0 else None)
        if name == "out_proj" and nd == 2:
            return P(m if leaf.shape[0] % rules.tp == 0 else None, None)
        if "gate_norm" in keys:
            return P(m if leaf.shape[0] % rules.tp == 0 else None)
        # --- everything else (norms, small projections) -------------------
        return P(*([None] * nd))

    # blocks are stacked with a leading layer dim — prepend None
    def with_layer_dim(path, leaf):
        keys = [str(getattr(p, "key", getattr(p, "name", p))) for p in path]
        s = spec_for(path, leaf)
        stacked = any(k in ("blocks", "enc_blocks", "dec_blocks") for k in keys)
        if stacked:
            inner = spec_for(path, _DropLead(leaf))
            return P(None, *inner)
        return s

    return jax.tree_util.tree_map_with_path(with_layer_dim, params_tree)


class _DropLead:
    """Shape proxy with the leading (layer) dim removed."""

    def __init__(self, leaf):
        self.shape = tuple(leaf.shape[1:])
        self.ndim = len(self.shape)


def batch_spec(rules: MeshRules, batch: int) -> tuple:
    """Returns the batch-dim sharding (or None when batch is too small)."""
    if batch % rules.n_batch == 0:
        return rules.batch_axes
    # try data axis only
    d = int(np.prod([rules.mesh.shape[a] for a in rules.batch_axes
                     if a == "data"]))
    if batch % d == 0:
        return ("data",)
    return None


def io_specs(cfg: ModelConfig, rules: MeshRules, batch: int):
    b = batch_spec(rules, batch)
    return {
        "tokens": P(b, None),
        "labels": P(b, None),
        "lengths": P(b),
        "frames": P(b, None, None),
        "prefix_embeds": P(b, None, None),
        "logits": P(b, rules.model_axis if cfg.vocab_size % rules.tp == 0 else None),
    }


def cache_specs(cfg: ModelConfig, rules: MeshRules, batch: int,
                cache_tree: Any):
    """Sharding for cache/state pytrees (transformer / rwkv / zamba /
    whisper). When batch can't be sharded, the KV sequence dim takes 'data'
    (split-K long-context decode). The transformer's stacked K/V is
    head-major, (L, B, Hkv, S, D): ``P(None, b, hkv, seq, None)``; zamba2's
    and whisper's stacked K/V stay (L|ninv, B, S, Hkv, D)."""
    b = batch_spec(rules, batch)
    seq = None if b is not None else "data"
    hkv = _head_axis(rules, cfg.num_kv_heads)
    m = rules.model_axis
    # the families transformer.py serves (models/api.py) stack K/V head-major
    head_major = cfg.family in ("dense", "moe", "vlm")

    def spec(path, leaf):
        keys = [str(getattr(p, "key", getattr(p, "name", p))) for p in path]
        name = keys[-1]
        nd = len(leaf.shape)
        if any(k in ("k", "v", "cross_k", "cross_v") for k in keys):
            # (L, B, Hkv, S, D) or (L|ninv, B, S, Hkv, D) stacked — or
            # (B, S, Hkv, D) for per-layer ring caches. When Hkv doesn't
            # divide the model axis, shard the *sequence* over 'model'
            # instead (split-K attention: softmax/AV reductions over the
            # sharded S become small per-token all-reduces under GSPMD) —
            # never replicate a multi-GB cache.
            if hkv is not None:
                inner = P(b, seq, hkv, None)
            else:
                s_axes = ("model",) if b is not None else ("data", "model")
                inner = P(b, s_axes, None, None)
            if nd == 5 and head_major:
                return P(None, inner[0], inner[2], inner[1], None)
            if nd == 5:
                return P(None, *inner)
            if inner[1] is not None and leaf.shape[1] % rules.tp != 0:
                return P(inner[0], None, *inner[2:])   # small ring: no shard
            return inner
        if name in ("ts_tm", "ts_cm"):               # (L, B, d)
            return P(None, b, m if cfg.d_model % rules.tp == 0 else None)
        if name == "wkv":                             # (L, B, H, hd, hd)
            h = leaf.shape[2]
            return P(None, b, m if h % rules.tp == 0 else None, None, None)
        if name in ("conv_x",):                       # (L, B, K-1, d_inner)
            return P(None, b, None, m if leaf.shape[-1] % rules.tp == 0 else None)
        if name in ("conv_bc",):
            return P(None, b, None, None)
        if name == "ssd":                              # (L, B, H, hd, N)
            h = leaf.shape[2]
            return P(None, b, m if h % rules.tp == 0 else None, None, None)
        return P(*([None] * nd))

    return jax.tree_util.tree_map_with_path(spec, cache_tree)
