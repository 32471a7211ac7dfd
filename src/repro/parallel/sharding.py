"""Logical sharding rules -> PartitionSpecs for params, optimizer, batches, caches.

Mesh axes:
  single-pod : ('data', 'model')            = (16, 16)
  multi-pod  : ('pod', 'data', 'model')     = (2, 16, 16)

Policy (baseline, see EXPERIMENTS.md §Perf for the hillclimbed variants):
  * batch          -> ('pod', 'data')   (pure DP across pods, ICI-local FSDP)
  * TP ("model")   -> heads / d_ff / vocab / experts
  * FSDP ("data")  -> the d_model axis of every weight matrix (ZeRO-3 style;
                      GSPMD inserts the per-layer all-gathers)
  * long-context decode (batch < data axis) -> KV-cache sequence dim on 'data'
    (sequence parallelism for the cache)

``param_pspecs`` is the single source of truth for which parameter axes are
``'model'``-sharded: besides the GSPMD launch path, the workload-lowering
pass (``repro.core.workloads``) consults it to divide each parameter's
gradient bytes by its tensor-parallel shard factor and to count the
``'model'``-sharded matmul pairs that emit TP collectives.  That consumer
passes a duck-typed mesh — only ``mesh.axis_names`` and ``mesh.shape``
(a name -> size mapping) are read by the rule functions; no devices are
required to evaluate the rules.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ArchConfig, ShapeSpec
from ..models import model as M

from .act import (BATCH, TP, _axis_size, _div, activation_mesh,  # noqa: F401
                  auto_axes, batch_axes, constrain, pick_tp_dim)

__all__ = ["batch_axes", "param_pspecs", "opt_pspecs", "batch_pspecs",
           "cache_pspecs", "to_shardings", "pick_tp_dim", "activation_mesh",
           "constrain", "BATCH", "TP"]

# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------

def _param_rule(name: str, shape: Tuple[int, ...], cfg: ArchConfig, mesh: Mesh,
                fsdp: str = "data") -> P:
    """Name+rank based PartitionSpec (leading dim may be the repeat axis)."""
    f = fsdp if _div(cfg.d_model, mesh, fsdp) else None

    def guard(spec: P, sh) -> P:
        # drop any axis assignment whose dim is not divisible
        out = []
        for dim, ax in zip(sh, tuple(spec) + (None,) * (len(sh) - len(spec))):
            if ax is None:
                out.append(None)
            else:
                axes = ax if isinstance(ax, tuple) else (ax,)
                size = int(np.prod([_axis_size(mesh, a) for a in axes]))
                out.append(ax if dim % size == 0 else None)
        return P(*out)

    if name == "embed":
        return guard(P("model", f), shape)
    if name == "head":
        return guard(P(f, "model"), shape)
    if name in ("final_norm",):
        return P()
    # block params: leading repeat axis
    body = shape[1:]
    if name in ("wq", "wk", "wv", "in_proj"):          # (D, out)
        return guard(P(None, f, "model"), shape)
    if name in ("wo", "out_proj"):                     # (in, D)
        return guard(P(None, "model", f), shape)
    ep = _div(cfg.n_experts, mesh, "model") if cfg.n_experts else False
    if name in ("wg", "wu"):
        if len(body) == 2:                              # dense mlp (D, F)
            return guard(P(None, f, "model"), shape)
        if ep:                                          # moe (E, D, F): EP
            return guard(P(None, "model", f, None), shape)
        return guard(P(None, None, f, "model"), shape)  # few experts: TP on F
    if name == "wd":
        if len(body) == 2:                              # dense mlp (F, D)
            return guard(P(None, "model", f), shape)
        if ep:
            return guard(P(None, "model", None, f), shape)
        return guard(P(None, None, "model", f), shape)
    if name == "router":                                # (D, E)
        return guard(P(None, f, None), shape)
    if name in ("conv_w",):                             # (K, Di)
        return guard(P(None, None, "model"), shape)
    if name in ("conv_b", "dt_bias", "D"):              # (Di,)
        return guard(P(None, "model"), shape)
    if name in ("x_proj", "A_log"):                     # (Di, *)
        return guard(P(None, "model", None), shape)
    if name == "dt_proj":                               # (dt_rank, Di)
        return guard(P(None, None, "model"), shape)
    if name in ("bq", "bk", "bv"):                      # (H*hd,)
        return guard(P(None, "model"), shape)
    if name.startswith("norm"):
        return P()
    return P()                                          # safe default: replicate


def param_pspecs(cfg: ArchConfig, mesh: Mesh) -> Dict[str, Any]:
    """PartitionSpec tree matching ``models.model.param_shapes(cfg)``.

    Args:
      cfg: the architecture; expert/TP divisibility guards read its widths.
      mesh: a ``jax.sharding.Mesh`` — or any object with ``.axis_names`` and
        ``.shape`` (name -> size), which is all the rules consult.

    Returns a tree with the same structure as ``param_shapes(cfg)`` whose
    leaves are ``PartitionSpec``s; zip-walking the two trees pairs every
    parameter shape with its spec (how ``repro.core.workloads`` derives
    per-parameter shard factors).
    """
    shapes = M.param_shapes(cfg)

    def walk(tree, name=""):
        if isinstance(tree, M.Shape):
            return _param_rule(name, tuple(tree), cfg, mesh)
        if isinstance(tree, dict):
            return {k: walk(v, k) for k, v in tree.items()}
        if isinstance(tree, (list, tuple)):
            return list(walk(v, name) for v in tree)
        return _param_rule(name, tuple(tree), cfg, mesh)

    return walk(shapes)


def opt_pspecs(cfg: ArchConfig, mesh: Mesh) -> Dict[str, Any]:
    ps = param_pspecs(cfg, mesh)
    return dict(m=ps, v=ps, step=P())


# --------------------------------------------------------------------------
# batches / caches
# --------------------------------------------------------------------------

def batch_pspecs(cfg: ArchConfig, shape: ShapeSpec, mesh: Mesh) -> Dict[str, P]:
    ba = batch_axes(mesh)
    bsz = int(np.prod([_axis_size(mesh, a) for a in ba]))
    b = ba if shape.global_batch % bsz == 0 else None
    if b is None and shape.global_batch % _axis_size(mesh, "data") == 0:
        b = ("data",)
    spec: Dict[str, P] = {}
    if cfg.frontend != "none":
        spec["embeds"] = P(b, None, None)
    else:
        spec["tokens"] = P(b, None)
    if shape.kind == "train":
        spec["labels"] = P(b, None)
    return spec


def cache_pspecs(cfg: ArchConfig, shape: ShapeSpec, mesh: Mesh) -> List[Dict]:
    """Per-pattern-position cache PartitionSpecs (leading repeat axis)."""
    ba = batch_axes(mesh)
    bsz = int(np.prod([_axis_size(mesh, a) for a in ba]))
    shard_batch = shape.global_batch % bsz == 0
    b = ba if shard_batch else None
    # long-context, tiny batch: sequence-parallel cache
    seq_ax = None if shard_batch else "data"
    out = []
    for spec in cfg.pattern:
        if spec.kind == "attn":
            from ..models.transformer import attn_cache_len
            L = attn_cache_len(cfg, spec, shape.seq_len)
            kv_ok = cfg.n_kv_heads % _axis_size(mesh, "model") == 0
            hd_ok = cfg.head_dim % _axis_size(mesh, "model") == 0
            heads = "model" if kv_ok else None
            hd = "model" if (not kv_ok and hd_ok) else None
            sax = seq_ax if (seq_ax and L % _axis_size(mesh, "data") == 0) else None
            out.append(dict(k=P(None, b, sax, heads, hd),
                            v=P(None, b, sax, heads, hd),
                            pos=P(None, sax)))
        else:
            di_ok = cfg.d_inner % _axis_size(mesh, "model") == 0
            di = "model" if di_ok else None
            out.append(dict(conv=P(None, b, None, di),
                            ssm=P(None, b, di, None)))
    return out


def to_shardings(pspecs, mesh: Mesh):
    mesh = auto_axes(mesh)
    return jax.tree.map(
        lambda p: NamedSharding(mesh, p),
        pspecs, is_leaf=lambda x: isinstance(x, P))
