"""Activation-sharding context.

Model code is mesh-agnostic; the launcher/dry-run wraps tracing in
:func:`activation_sharding` so that :func:`constrain` can place
``with_sharding_constraint`` on the hot activations (residual-stream scan
carry, logits) with the right axis names for whichever mesh is in use.
Outside the context (CPU unit tests) ``constrain`` is a no-op.

The key constraint is **sequence parallelism on the residual stream**: the
scan carry ``x [B, S, d]`` is sharded over the TP axis along S between
layers, which cuts stored-activation memory (and the remat carry) by the
TP degree; GSPMD inserts the gather where attention needs the full
sequence.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Sequence, Tuple, Union

import jax
from jax.sharding import PartitionSpec as P

_STATE = threading.local()

AxisRef = Union[None, str, Tuple[str, ...]]  # "dp" / "tp" resolved below


@contextlib.contextmanager
def activation_sharding(mesh, dp_axes: Tuple[str, ...], tp_axis,
                        vocab_axis=None):
    """``vocab_axis`` defaults to ``tp_axis``; under tp_scope="vocab" the
    layer carries see tp=None while logits still shard over the model axis."""
    prev = getattr(_STATE, "ctx", None)
    _STATE.ctx = (mesh, tuple(dp_axes), tp_axis,
                  vocab_axis if vocab_axis is not None else tp_axis)
    try:
        yield
    finally:
        _STATE.ctx = prev


def _resolve(entry, dp_axes, tp_axis, vocab_axis):
    if entry == "dp":
        return dp_axes
    if entry == "tp":
        return tp_axis
    if entry == "vocab":
        return vocab_axis
    return entry


def _size(mesh, axes) -> int:
    names = (axes,) if isinstance(axes, str) else tuple(axes or ())
    size = 1
    for a in names:
        size *= mesh.shape[a]
    return size


def current_dp_size() -> int:
    """Product of the data-parallel axis sizes (1 outside a context)."""
    ctx = getattr(_STATE, "ctx", None)
    return 1 if ctx is None else _size(ctx[0], ctx[1])


def _spec(ctx, shape, spec_kinds) -> P:
    """``spec_kinds`` resolved against the context, each entry dropped
    where it does not evenly divide its dim."""
    mesh, dp_axes, tp_axis, vocab_axis = ctx
    entries = []
    for dim, kind in zip(shape, spec_kinds):
        axes = _resolve(kind, dp_axes, tp_axis, vocab_axis)
        entries.append(axes if axes is not None and dim % _size(mesh, axes) == 0
                       else None)
    return P(*entries)


def constrain(x: jax.Array, spec_kinds: Sequence) -> jax.Array:
    """Apply a sharding constraint if a context is active and divisible.

    ``spec_kinds`` entries: "dp", "tp", None, or explicit axis names.
    Entries that do not evenly divide their dim are dropped.
    """
    ctx = getattr(_STATE, "ctx", None)
    if ctx is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(ctx[0], _spec(ctx, x.shape, spec_kinds))
    )


def map_kv_shards(fn, cache, new, position: jax.Array):
    """``fn(cache, new, position)`` on each device's shard of a
    positions-last K/V cache, or on the whole cache outside a context.

    ``cache`` leaves are ``[L, B, KV, Dh, T]`` with B over the data axes
    and T over the model axis, as ``specs.cache_shardings`` places them;
    ``new`` leaves are ``[L, B, KV, Dh]`` and ``position`` is ``[B]``. Each
    shard sees ``position`` relative to its own first position, so a
    position held by another device falls outside its position axis.
    """
    ctx = getattr(_STATE, "ctx", None)
    if ctx is None:
        return fn(cache, new, position)
    mesh = ctx[0]
    shape = jax.tree.leaves(cache)[0].shape
    # T goes over the model axis, which the context keeps as its vocab axis
    # also where the layers see no model axis (``tp_scope="vocab"``).
    cache_spec = _spec(ctx, shape, (None, "dp", None, None, "vocab"))
    slots, positions = cache_spec[1], cache_spec[4]

    def local(cache, new, position):
        if positions is not None:
            first = jax.lax.axis_index(positions) * (
                shape[-1] // _size(mesh, positions)
            )
            position = position - first
        return fn(cache, new, position)

    return jax.shard_map(
        local, mesh=mesh, in_specs=(cache_spec, P(None, slots), P(slots)),
        out_specs=cache_spec,
    )(cache, new, position)
