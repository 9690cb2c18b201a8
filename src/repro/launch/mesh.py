"""Production mesh + per-architecture sharding policy.

``make_production_mesh`` is a function (never a module-level constant) so
importing this module never touches jax device state.

``rules_for`` resolves the logical-axis -> mesh-axis rule table per
(architecture x mesh):

  * attention: TP over heads when n_heads divides the model axis; otherwise
    sequence-parallel attention (activations sharded on S over 'model',
    KV gathered per layer) so compute still scales 1/(data*model);
  * decode: when heads cannot shard, the KV cache length axis shards over
    'model' instead (each device scans 1/16th of the cache);
  * MoE: expert-parallel (expert axis over 'model') when E divides the
    model axis, else TP-MoE (expert ffn width over 'model');
  * fsdp: weight embed-axis additionally sharded over the data axes
    (ZeRO-3-style), used by the >30B archs.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
from jax.sharding import AxisType, Mesh

from ..models.config import ModelConfig
from ..models.params import sharding_rules


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def rules_for(cfg: ModelConfig, mesh: Mesh, *, kind: str = "train") -> Dict:
    multi_pod = "pod" in mesh.axis_names
    msize = mesh.shape.get("model", 1)
    rules = sharding_rules(fsdp=cfg.fsdp, multi_pod=multi_pod)

    heads_ok = cfg.n_heads_eff % msize == 0
    if not heads_ok:
        rules["act_heads"] = None
        rules["act_kv_heads"] = None
        rules["heads"] = None          # attention weights replicated over TP
        if kind == "decode":
            rules["act_cache_len"] = "model"   # shard the KV cache length
        else:
            rules["act_seq"] = "model"         # sequence-parallel attention
    else:
        if cfg.n_kv_heads % msize != 0:
            rules["act_kv_heads"] = None
            rules["kv_heads"] = None
        if kind == "decode":
            rules["act_cache_len"] = None

    if cfg.moe is not None and cfg.moe.num_experts % msize != 0:
        rules["expert"] = None
        rules["expert_mlp"] = "model"  # TP-MoE width sharding
    return rules
