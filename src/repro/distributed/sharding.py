"""Logical-axis -> mesh-axis sharding rules.

Every parameter / cache leaf carries a tuple of logical axis names (PSpec).
A rule table maps logical names to an ordered preference of mesh axes; the
resolver assigns mesh axes per array under two constraints:

  * a mesh axis is used at most once per array, and
  * the dimension must divide by the product of the assigned axis sizes
    (falls back to fewer axes / replication otherwise).

This one mechanism expresses DP, FSDP/ZeRO (embed->data), TP (heads/mlp/
vocab/experts->model), EP (experts->model), and sequence sharding for long-
context decode (seq_kv->(data,model): the data axis is free when batch=1,
giving 256-way KV sharding for ``long_500k``, and falls back to model-only
for ``decode_32k`` where data is consumed by the batch).
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.params import PSpec, is_pspec

AxisPref = Tuple[str, ...]
Rules = Dict[str, AxisPref]


def make_rules(multi_pod: bool, *, fsdp: bool = True,
               model_axis: str = "model") -> Rules:
    batch = ("pod", "data") if multi_pod else ("data",)
    fsdp_axes = ("data",) if fsdp else ()
    m = (model_axis,)
    return {
        # params
        "experts": m,
        "q_heads": m,
        "kv_heads": m,
        "vocab": m + fsdp_axes,       # falls back to fsdp if not divisible
        "mlp": m,
        # kv_lora is a CONTRACTION dim in MLA attention: sharding it over
        # model forces a psum per flash block (measured +28 s/chip
        # collective on deepseek-v2 train).  FSDP-shard it instead; heads
        # carry the TP.
        "kv_lora": fsdp_axes,
        "q_lora": fsdp_axes,          # likewise contracted by w_uq
        "ssm_heads": m,
        "ssm_in": m,
        "embed": fsdp_axes,           # FSDP / ZeRO shard dim
        "head_dim": (),
        "layers": (),                 # scan dim — never sharded
        # activations / caches
        "batch": batch,
        "seq_kv": ("data", model_axis),
        "seq_enc": (model_axis,),
        # flattened token dim entering the EP all-to-all region: sharded
        # over batch x model so the cotangent reshard does not trigger
        # XLA's "involuntary full rematerialization" (phi3.5 train fix)
        "tokens": batch + m,
    }


def spec_for(axes: Tuple[Optional[str], ...], rules: Rules,
             mesh: Mesh, shape: Tuple[int, ...]) -> P:
    used = set()
    parts = []
    for dim, name in zip(shape, axes):
        assigned: Tuple[str, ...] = ()
        if name is not None:
            prefs = rules.get(name, ())
            size = 1
            for ax in prefs:
                if ax in used or ax not in mesh.shape:
                    continue
                if dim % (size * mesh.shape[ax]) == 0:
                    assigned = assigned + (ax,)
                    size *= mesh.shape[ax]
                    used.add(ax)
        if len(assigned) == 0:
            parts.append(None)
        elif len(assigned) == 1:
            parts.append(assigned[0])
        else:
            parts.append(assigned)
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def shardings_for(tree_pspec, mesh: Mesh, rules: Rules):
    """PSpec tree -> NamedSharding tree (same structure, PSpec stripped)."""
    def f(p: PSpec):
        shape = tuple(p.value.shape)
        return NamedSharding(mesh, spec_for(p.axes, rules, mesh, shape))
    return jax.tree.map(f, tree_pspec, is_leaf=is_pspec)


def tree_device_bytes(tree_pspec, mesh: Mesh, rules: Rules) -> int:
    """Exact per-device resident bytes of a PSpec tree under the rules
    (shape product x dtype size / shard factor)."""
    import numpy as np

    def f(p: PSpec) -> int:
        shape = tuple(p.value.shape)
        spec = spec_for(p.axes, rules, mesh, shape)
        shards = 1
        for entry in spec:
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            for a in axes:
                shards *= mesh.shape[a]
        return int(np.prod(shape) * p.value.dtype.itemsize // max(shards, 1))

    return sum(jax.tree.leaves(jax.tree.map(f, tree_pspec,
                                            is_leaf=is_pspec)))


# ---------------------------------------------------------------------------
# Data-plane (Morpheus serving) placement
# ---------------------------------------------------------------------------

def plane_state_shardings(state, mesh: Mesh,
                          instr_axes: Tuple[str, ...] = ("data",)):
    """Per-leaf ``NamedSharding`` prefix for a ``PlaneState``:

      * ``tables`` — replicated (every device serves lookups against a
        full copy of the match-action maps; control-plane pushes refresh
        all replicas at once),
      * ``instr``  — device-local (each sketch leaf carries a leading
        shard axis laid out over ``instr_axes``; devices record their own
        traffic, merged only at plan time),
      * ``guards`` — replicated (the in-graph RW guard is a broadcast
        flag).

    The returned object is itself a ``PlaneState`` (of shardings), which
    is a valid pytree prefix for ``MorpheusEngine.compile``'s
    ``in_shardings``/``out_shardings``."""
    rep = NamedSharding(mesh, P())
    local = NamedSharding(mesh, P(tuple(instr_axes)))
    return state.replace(
        tables=jax.tree.map(lambda _: rep, state.tables),
        instr=jax.tree.map(lambda _: local, state.instr),
        guards=jax.tree.map(lambda _: rep, state.guards))


def plane_batch_shardings(batch, mesh: Mesh,
                          axes: Tuple[str, ...] = ("data",),
                          stacked: bool = False):
    """Request-batch placement for the serving data plane: leading
    (batch) dim sharded over ``axes`` when divisible, scalars and
    indivisible leaves replicated.  With ``stacked=True`` (fused K-step
    windows) each leaf carries a leading window axis that stays
    *unsharded* — it is the ``lax.scan`` loop dim — and the per-step
    batch dim underneath it gets the ``axes`` placement."""
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    d = 1 if stacked else 0
    lead = (None,) * d

    def f(x):
        shape = getattr(x, "shape", ())
        if len(shape) >= d + 1 and shape[d] % n == 0:
            return NamedSharding(mesh, P(*lead, tuple(axes)))
        return NamedSharding(mesh, P())

    return jax.tree.map(f, batch)


def batch_shardings(batch_specs: dict, mesh: Mesh, rules: Rules):
    """Data-batch inputs: shard the leading (batch) dim; pos scalars are
    replicated."""
    out = {}
    for k, v in batch_specs.items():
        if v.ndim == 0:
            out[k] = NamedSharding(mesh, P())
        else:
            axes = ("batch",) + (None,) * (v.ndim - 1)
            out[k] = NamedSharding(mesh,
                                   spec_for(axes, rules, mesh, v.shape))
    return out
