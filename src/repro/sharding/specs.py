"""Sharding policy: PartitionSpecs for params, optimizer state, batches,
and caches — DP / FSDP / TP / EP / SP composed per architecture.

Rules are *name- and shape-based* with divisibility sanitisation: a spec
axis that does not evenly divide the corresponding dimension is dropped
(XLA requires even input sharding; intermediates may still shard unevenly
under GSPMD). This is what lets one policy cover all ten archs — e.g.
mamba2's vocab 50280 is not 16-divisible, so the embed table falls back
to sharding d_model on the TP axis.

Default placement (hillclimbed variants live in perf configs):
  * 2-D weights [d_in, d_out]: column-parallel on the TP axis for
    up-projections, row-parallel for down/out-projections; FSDP shards
    the *other* dim over the data axes for large models.
  * MoE expert stacks [E, ...]: expert-parallel on the TP axis when E
    divides it, otherwise tensor-parallel within experts.
  * Embeddings [V, d]: vocab-parallel (falls back to d).
  * Batches: [B, ...] over (pod, data); KV caches ([L, B, KV, Dh, T],
    positions last) shard T on the TP axis for decode (B already covers
    the data axes), SSM states shard heads.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.api import ShapeSpec
from repro.models.config import ModelConfig


@dataclasses.dataclass(frozen=True)
class ShardingPolicy:
    tp_axis: Optional[str] = "model"
    dp_axes: Tuple[str, ...] = ("data",)          # + "pod" on the multipod mesh
    fsdp: bool = True                              # shard params over dp axes too
    fsdp_min_params: int = 2_000_000_000           # only FSDP models above this
    expert_parallel: bool = True                   # EP over tp_axis when divisible
    shard_kv_seq: bool = True                      # decode KV cache: T over TP axis
    # tp_enabled=False → pure DP/FSDP: the "model" axis joins the data axes
    # (the right policy for small models whose TP matmuls are sliver-thin).
    tp_enabled: bool = True
    # tp_scope="vocab" keeps the model axis OUT of the layer matmuls (they
    # run data-parallel) but still vocab-shards the embedding table and the
    # CE logits — the largest tensors of a small-model train step. The
    # batch then shards over the data axes only.
    tp_scope: str = "full"            # full | vocab

    def for_mesh(self, mesh: Mesh) -> "ShardingPolicy":
        dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        if not self.tp_enabled:
            dp = dp + ("model",)
            return dataclasses.replace(self, dp_axes=dp, tp_axis=None)
        return dataclasses.replace(self, dp_axes=dp)


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return size


def sanitize_spec(spec: P, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """Drop spec entries that do not divide their dimension evenly."""
    entries = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, axes in zip(shape, entries):
        if axes is None:
            out.append(None)
        elif dim % _axis_size(mesh, axes) == 0:
            out.append(axes)
        else:
            out.append(None)
    return P(*out)


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------

_COLUMN_PARALLEL = (  # [d_model, X] → shard X on TP
    "wq", "wk", "wv", "bq", "bk", "bv", "w_gate", "w_up",
    "in_proj_z", "in_proj_xbc", "in_proj_dt",
)
_ROW_PARALLEL = ("wo", "w_down", "out_proj")  # [X, d_model] → shard X on TP


def param_spec(
    cfg: ModelConfig,
    policy: ShardingPolicy,
    mesh: Mesh,
    path: Tuple[str, ...],
    shape: Tuple[int, ...],
) -> P:
    names = [p for p in path]
    leaf = names[-1]
    fsdp_on = policy.fsdp and cfg.param_count() >= policy.fsdp_min_params
    fsdp: Optional[Tuple[str, ...]] = policy.dp_axes if fsdp_on else None
    tp = policy.tp_axis
    if policy.tp_scope == "vocab" and leaf not in ("table",):
        # Layer weights run data-parallel; FSDP may use the idle model axis.
        tp = None
        if fsdp is not None:
            fsdp = fsdp + ((policy.tp_axis,) if policy.tp_axis else ())

    # Stacked layer dims (scan over periods / encoder / decoder stacks).
    stacked = any(n in ("blocks", "encoder", "decoder") for n in names[:-1])
    lead: Tuple = (None,) if stacked else ()

    def make(*entries) -> P:
        return sanitize_spec(P(*lead, *entries), shape, mesh)

    ndim = len(shape) - len(lead)

    if leaf == "table":  # embedding / lm_head [V, d]
        return make(tp, fsdp)
    if leaf in ("enc_pos", "dec_pos"):
        return make(None, tp)
    if ndim <= 1:
        # Norm scales, biases (except qkv bias handled below), scalars.
        if leaf in ("bq", "bk", "bv"):
            return make(tp)
        return make(None)
    if leaf == "router":
        return make(fsdp, None)
    if ndim == 3:  # MoE expert stacks [E, in, out]
        # NEVER shard the contracting (middle) dim: doing so turns every
        # expert matmul into activation-sized partial-sum all-reduces
        # ([E,C,f]-shaped) over the fsdp axis — measured ~10× the wire of
        # the weight gathers this layout incurs instead (§Perf, jamba
        # iteration 3). FSDP shards the *output* dim.
        e = shape[len(lead)]
        if policy.expert_parallel and tp is not None and e % _axis_size(mesh, tp) == 0:
            # Megatron pairing within each expert over the fsdp axis:
            # gate/up column-parallel on f, w_down row-parallel on f —
            # the only cross-device sum is the [.., d] output (3× smaller
            # than gathering the f-wide hidden).
            if leaf == "w_down":
                return make(tp, fsdp, None)
            return make(tp, None, fsdp)
        # Non-EP fallback (expert count not TP-divisible, e.g. grok's 8
        # experts on a 16-way axis): Megatron within experts over TP —
        # measured better than output-dim sharding here, since without EP
        # the buffer would otherwise be fully gathered per device
        # (§Perf: grok iteration log, refuted generalisation).
        if leaf in ("w_gate", "w_up"):
            return make(None, fsdp, tp)
        return make(None, tp, fsdp)
    if leaf in _COLUMN_PARALLEL:
        return make(fsdp, tp)
    if leaf in _ROW_PARALLEL:
        return make(tp, fsdp)
    if leaf == "conv_w":  # [W, conv_dim]
        return make(None, tp)
    # Fallback: replicate.
    return make(*([None] * ndim))


def param_shardings(
    cfg: ModelConfig,
    policy: ShardingPolicy,
    mesh: Mesh,
    params_shapes: Any,
) -> Any:
    """Tree of NamedShardings matching a params (shape) tree."""

    def visit(path, leaf):
        names = tuple(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        spec = param_spec(cfg, policy, mesh, names, tuple(leaf.shape))
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(visit, params_shapes)


# ---------------------------------------------------------------------------
# Batch / cache specs
# ---------------------------------------------------------------------------


def batch_shardings(
    cfg: ModelConfig,
    policy: ShardingPolicy,
    mesh: Mesh,
    shape_spec: ShapeSpec,
    batch_shapes: Dict[str, jax.ShapeDtypeStruct],
) -> Dict[str, NamedSharding]:
    dp = policy.dp_axes
    out: Dict[str, NamedSharding] = {}
    for name, sds in batch_shapes.items():
        if name in ("tokens", "mask"):
            spec = P(dp, None)
        elif name == "frames":       # [B, S, d]
            spec = P(dp, None, policy.tp_axis)
        elif name == "embeds":
            spec = P(dp, None, policy.tp_axis)
        elif name in ("token", "position"):  # decode step [B]
            spec = P(dp)
        else:
            spec = P()
        out[name] = NamedSharding(mesh, sanitize_spec(spec, sds.shape, mesh))
    return out


def cache_shardings(
    cfg: ModelConfig,
    policy: ShardingPolicy,
    mesh: Mesh,
    cache_shapes: Any,
) -> Any:
    """KV caches: [L, B, KV, Dh, T] (positions last) — B over dp, T over TP
    (flash-decode style sequence sharding; GSPMD handles the softmax
    reduction). SSM states: [L, B, H, P, N] — H over TP. Conv caches:
    channel over TP."""
    dp = policy.dp_axes
    tp = policy.tp_axis

    def visit(path, leaf):
        names = tuple(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        leafname = names[-1]
        shape = tuple(leaf.shape)
        kv_names = ("k", "v", "self_k", "self_v", "cross_k", "cross_v")
        is_int8 = len(names) >= 2 and names[-2] in kv_names
        if leafname in kv_names or (leafname in ("q", "scale") and is_int8):
            # int8 KV cache: q mirrors the KV layout; scale has head_dim 1.
            seq = tp if policy.shard_kv_seq else None
            spec = P(None, dp, None, None, seq)
        elif leafname == "ssm":      # [L, B, H, P, N]
            spec = P(None, dp, tp, None, None)
        elif leafname == "conv":     # [L, B, W-1, C]
            spec = P(None, dp, None, tp)
        else:
            spec = P(*([None] * len(shape)))
        return NamedSharding(mesh, sanitize_spec(spec, shape, mesh))

    return jax.tree_util.tree_map_with_path(visit, cache_shapes)
