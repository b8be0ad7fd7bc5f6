"""Path-based parameter / input sharding rules (MaxText-style logical axes).

Every rule maps a parameter path suffix to an ordered list of *candidate*
logical specs; ``resolve_spec`` applies divisibility fallbacks per mesh, and
we pick the candidate that keeps the most dims sharded.  This single table
covers all ten assigned architectures (dense / MoE / MLA / Mamba / RWKV) on
both the single-pod (data, model) and multi-pod (pod, data, model) meshes.
"""
from __future__ import annotations

import re
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.tree_util import tree_map_with_path, keystr

from .context import resolve_spec

Spec = Tuple[Optional[str], ...]

# (regex on /-joined path, [candidate logical specs for the unstacked rank])
PARAM_RULES = [
    (r"(^|/)embed/table$", [("tensor", "fsdp"), (None, "fsdp")]),
    (r"(^|/)frontend/w$", [("fsdp", "tensor")]),
    (r"(^|/)unembed/w$", [("fsdp", "tensor")]),
    (r"(^|/)(wq|wk|wv|wq_b|wk_b|wv_b|wi_gate|wi_up|in_proj|wr6|wk6|wv6|wg6)$",
     [("fsdp", "tensor")]),
    (r"(^|/)(wq_a|wkv_a)$", [("fsdp", "tensor"), ("fsdp", None)]),
    (r"(^|/)(wo|out_proj|wo6)$", [("tensor", "fsdp")]),
    (r"(^|/)router/w$", [(None, None)]),
    (r"(^|/)experts/(w_gate|w_up)$",
     [("expert", "fsdp", None), (None, "fsdp", "tensor")]),
    (r"(^|/)experts/w_down$",
     [("expert", None, "fsdp"), (None, "tensor", "fsdp")]),
    # mamba
    (r"(^|/)conv_w$", [(None, "tensor")]),
    (r"(^|/)(conv_b|dt_b|Dskip)$", [("tensor",)]),
    (r"(^|/)x_proj$", [("tensor", None)]),
    (r"(^|/)dt_w$", [(None, "tensor")]),
    (r"(^|/)A_log$", [("tensor", None)]),
    # rwkv loras / mixes
    (r"(^|/)lora_w1$", [("fsdp", None)]),
    (r"(^|/)lora_w2$", [(None, "tensor")]),
    (r"(^|/)(w0|u|mu_.*)$", [(None,) * 8]),  # trimmed to rank below
    (r"scale$", [(None,)]),
]

INPUT_RULES = [
    (r"(^|/)(tokens|labels|loss_mask|frame_labels|frame_mask|positions)$",
     [("batch", None)]),
    (r"(^|/)(features|image_embeds)$", [("batch", None, None)]),
    (r"(^|/)(k|v)$", [("batch", "kv_len", "tensor", None)]),
    (r"(^|/)(ckv|kpe)$", [("batch", "kv_len", None)]),
    (r"(^|/)conv$", [("batch", None, "tensor")]),
    (r"(^|/)ssm$", [("batch", "tensor", None)]),
    (r"(^|/)wkv$", [("batch", None, None, None)]),
    (r"(^|/)(tm_shift|cm_shift)$", [("batch", None)]),
]


def _match(path: str, rules) -> Optional[list]:
    for pat, cands in rules:
        if re.search(pat, path):
            return cands
    return None


def _pick(cands, shape, mesh: Mesh, stacked: bool) -> P:
    best, best_n = P(*([None] * len(shape))), -1
    for cand in cands:
        cand = tuple(cand)[: len(shape) - (1 if stacked else 0)]
        if stacked:
            cand = (None,) + cand
        cand = cand + (None,) * (len(shape) - len(cand))
        spec = resolve_spec(cand, shape, mesh)
        n = sum(e is not None for e in spec)
        if n > best_n:
            best, best_n = spec, n
    return best


def _spec_for(path_str: str, leaf, mesh: Mesh, rules) -> P:
    shape = leaf.shape
    cands = _match(path_str, rules)
    # scanned stacks carry a leading `repeats` dim
    stacked = bool(re.search(r"(^|/)(layers|caches)/", path_str))
    if cands is None:
        return P(*([None] * len(shape)))
    return _pick(cands, shape, mesh, stacked)


def _path_str(kp) -> str:
    return keystr(kp, simple=True, separator="/")


def param_specs(params, mesh: Mesh):
    return tree_map_with_path(
        lambda kp, x: _spec_for(_path_str(kp), x, mesh, PARAM_RULES), params)


def param_shardings(params, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_specs(params, mesh))


def batch_spec(inputs, mesh: Mesh):
    def leaf(kp, x):
        if not hasattr(x, "shape") or x.ndim == 0:
            return P()
        path = _path_str(kp)
        cands = _match(path, INPUT_RULES)
        stacked = bool(re.search(r"(^|/)(layers|caches)/", path))
        if cands is None:
            # default: shard the leading (batch) dim
            cand = ("batch",) + (None,) * (x.ndim - 1)
            return _pick([cand], x.shape, mesh, False)
        return _pick(cands, x.shape, mesh, stacked)
    return tree_map_with_path(leaf, inputs)


def input_shardings(inputs, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        batch_spec(inputs, mesh))


def constrain_like_params(tree):
    """Pin a pytree (e.g. grads) to the parameter sharding rules inside the
    active mesh context — forces reduce-scatter instead of all-reduce on
    the backward pass so grads never materialize replicated."""
    from .context import active_mesh
    mesh = active_mesh()
    if mesh is None:
        return tree
    specs = param_specs(tree, mesh)
    return jax.tree.map(
        lambda x, s: jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, s)), tree, specs)
