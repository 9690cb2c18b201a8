"""Parameter metadata and sharding rules.

Every parameter carries *logical* axis names (MaxText-style); a rule table
maps logical axes to mesh axes, so DP / FSDP / TP / EP are configuration,
not model code.  `param_specs` trees mirror the param pytree; shardings are
derived per-mesh with `make_shardings`.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Pytree = Any


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    logical_axes: Tuple[Optional[str], ...]
    dtype: Any = jnp.bfloat16
    init: str = "normal"          # normal | zeros | ones | scaled
    init_scale: float = 1.0

    def __post_init__(self):
        assert len(self.shape) == len(self.logical_axes), (self.shape, self.logical_axes)


# Default logical-axis -> mesh-axis rules.  `fsdp` adds data-axis sharding on
# the weights' embed axis (ZeRO-3-style); optimizer state follows params.
def sharding_rules(*, fsdp: bool = False, multi_pod: bool = False) -> Dict[str, Any]:
    fsdp_axes: Tuple[str, ...] = ()
    if fsdp:
        fsdp_axes = (("pod", "data") if multi_pod else ("data",))
    return {
        # weight axes
        "embed": fsdp_axes or None,     # d_model rows of weight matrices
        "mlp": "model",                 # ffn hidden
        "heads": "model",               # attention heads (fused q dim)
        "kv_heads": None,               # kv heads often < mesh; replicate
        "vocab": "model",               # embedding/output vocab
        "expert": "model",              # MoE expert axis (EP)
        "expert_mlp": None,
        "layers": None,
        "conv": None,
        "state": None,
        "head_dim": None,
        # activation axes
        "act_batch": ("pod", "data") if multi_pod else ("data",),
        "act_seq": None,                # "model" => sequence-parallel attention
        "act_embed": None,
        "act_heads": "model",
        "act_kv_heads": "model",
        "act_vocab": "model",
        "act_cache_len": None,          # "model" => decode KV cache sharded on S
    }


def logical_to_spec(axes: Tuple[Optional[str], ...], rules: Dict[str, Any]) -> P:
    parts = []
    used = set()
    for ax in axes:
        r = rules.get(ax) if ax is not None else None
        # never map two tensor dims onto the same mesh axis
        if r is not None:
            flat = (r,) if isinstance(r, str) else tuple(r)
            if any(f in used for f in flat):
                r = None
            else:
                used.update(flat)
        parts.append(r)
    return P(*parts)


def make_shardings(specs: Pytree, mesh: Mesh, rules: Dict[str, Any]) -> Pytree:
    def one(s: ParamSpec):
        spec = logical_to_spec(s.logical_axes, rules)
        # drop mesh axes that do not divide the dim (e.g. tiny smoke configs)
        fixed = []
        for dim, part in zip(s.shape, spec + (None,) * (len(s.shape) - len(spec))):
            if part is None:
                fixed.append(None)
                continue
            axes = (part,) if isinstance(part, str) else tuple(part)
            size = math.prod(mesh.shape[a] for a in axes)
            fixed.append(part if dim % size == 0 else None)
        return NamedSharding(mesh, P(*fixed))

    return jax.tree.map(one, specs, is_leaf=lambda x: isinstance(x, ParamSpec))


def init_params(specs: Pytree, rng: jax.Array) -> Pytree:
    leaves, treedef = jax.tree.flatten(specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    keys = jax.random.split(rng, len(leaves))

    def one(s: ParamSpec, key):
        if s.init == "zeros":
            return jnp.zeros(s.shape, s.dtype)
        if s.init == "ones":
            return jnp.ones(s.shape, s.dtype)
        scale = s.init_scale
        if s.init == "scaled":  # fan-in scaled
            fan_in = s.shape[-2] if len(s.shape) >= 2 else s.shape[-1]
            scale = s.init_scale / math.sqrt(max(fan_in, 1))
        return (jax.random.normal(key, s.shape, jnp.float32) * scale).astype(s.dtype)

    return jax.tree.unflatten(treedef, [one(s, k) for s, k in zip(leaves, keys)])


def abstract_params(specs: Pytree) -> Pytree:
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
        specs,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )


def param_count(specs: Pytree) -> int:
    leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    return sum(math.prod(s.shape) for s in leaves)


def constrain(x: jax.Array, rules: Dict[str, Any], *axes: Optional[str]) -> jax.Array:
    """with_sharding_constraint by logical activation axes (no-op outside a
    ``jax.set_mesh`` context).  A dimension the mesh axes do not divide is
    left unconstrained."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    spec = logical_to_spec(tuple(axes), rules)
    fixed = []
    for dim, part in zip(x.shape, spec + (None,) * (len(x.shape) - len(spec))):
        if part is None:
            fixed.append(None)
            continue
        axs = (part,) if isinstance(part, str) else tuple(part)
        size = math.prod(mesh.shape.get(a, 1) for a in axs)
        fixed.append(part if dim % size == 0 else None)
    return jax.lax.with_sharding_constraint(x, P(*fixed))
