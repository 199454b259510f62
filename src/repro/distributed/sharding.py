"""Logical-axis sharding rules (MaxText-style) for the repro framework.

Model code annotates tensors with *logical* axis names ("batch", "embed",
"q_heads", ...). A rule table maps logical names to physical mesh axes.
Rules are installed with the ``axis_rules`` context manager; when no rules
are active (e.g. single-device smoke tests) every annotation is a no-op.

FSDP+TP layout (see DESIGN.md §6):
  - params' embed dim            -> fsdp axes ("data",) or ("pod","data")
  - heads / mlp / vocab /experts -> "model" (TP / EP)
  - activations' batch           -> ("data",) or ("pod","data")
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

AxisVal = Union[None, str, Tuple[str, ...]]

_state = threading.local()


def _current() -> Optional[Dict[str, AxisVal]]:
    return getattr(_state, "rules", None)


def current_mesh() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


@contextlib.contextmanager
def axis_rules(rules: Dict[str, AxisVal], mesh: Optional[Mesh] = None):
    prev_r = getattr(_state, "rules", None)
    prev_m = getattr(_state, "mesh", None)
    _state.rules = rules
    _state.mesh = mesh
    try:
        yield
    finally:
        _state.rules = prev_r
        _state.mesh = prev_m


# ---------------------------------------------------------------------------
# Rule tables
# ---------------------------------------------------------------------------

def make_rules(*, multi_pod: bool = False,
               shard_attn_heads: bool = True,
               fsdp: bool = True,
               overrides: Optional[Dict[str, AxisVal]] = None) -> Dict[str, AxisVal]:
    """Default logical->physical table for the production meshes."""
    dp: AxisVal = ("pod", "data") if multi_pod else ("data",)
    fs: AxisVal = dp if fsdp else None
    rules: Dict[str, AxisVal] = {
        # --- parameters -----------------------------------------------
        "embed": fs,           # FSDP: shard d_model dim of weights over data
        "q_heads": "model" if shard_attn_heads else None,
        "kv_heads": None,      # kv heads in {1,8,16} -> replicated under TP=16
        "head_dim": None,
        "mlp": "model",
        "vocab": "model",
        "experts": "model",    # EP
        "expert_embed": fs,    # FSDP dim of expert weights (gathered in block)
        "expert_mlp": None,
        "rnn": "model",        # RG-LRU width TP (elementwise recurrence)
        "ssm_heads": "model",  # mamba heads TP
        "ssm_state": None,
        "conv": None,
        "layers": None,        # scan axis, never sharded
        # --- activations ----------------------------------------------
        "batch": dp,
        "seq": None,
        "cache_seq": None,   # decode overrides: ('model',) flash-decode
        # sequence-parallel residual stream (Korthikanti-style): shard the
        # seq dim of the residual over 'model' between TP blocks, turning
        # activation all-reduces into reduce-scatter + on-demand gathers.
        # Off by default; enabled per-cell in §Perf hillclimbs.
        "residual_seq": None,
        "act_embed": None,
        "act_heads": "model" if shard_attn_heads else None,
        "act_kv_heads": None,
        "act_mlp": "model",
        "act_vocab": "model",
        "act_rnn": "model",
        "act_ssm_heads": "model",
    }
    if overrides:
        rules.update(overrides)
    return rules


def rules_for_config(cfg, *, multi_pod: bool = False,
                     overrides: Optional[Dict[str, AxisVal]] = None) -> Dict[str, AxisVal]:
    return make_rules(multi_pod=multi_pod,
                      shard_attn_heads=cfg.shard_attn_heads,
                      overrides=overrides)


SERVING_MESH_AXES: Tuple[str, ...] = ("data",)


def serving_rules(overrides: Optional[Dict[str, AxisVal]] = None
                  ) -> Dict[str, AxisVal]:
    """Logical->physical table for the *serving* mesh (a 1-D "data" axis
    over the inference devices). Trunk embed is data-parallel: activation
    batches split over "data" while every weight axis stays replicated —
    the trunks the zoo serves are small enough that staging one copy per
    device is cheaper than cross-device weight gathers on the hot path.
    """
    rules: Dict[str, AxisVal] = {
        # trunk weights: replicated (staged once per device via the
        # batch-invariant NamedSharding below)
        "embed": None,          # input width dim of W / centers
        "mlp": None,            # output width dim of W
        "vocab": None,
        # activations: rows split across the mesh
        "batch": ("data",),
        "act_embed": None,
    }
    if overrides:
        rules.update(overrides)
    return rules


def serving_batch_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding of a [rows, width] activation batch on the serving mesh."""
    return named_sharding(mesh, ("batch", "act_embed"), serving_rules())


def serving_weight_sharding(mesh: Mesh, ndim: int) -> NamedSharding:
    """Replicated sharding for a staged weight tensor (any rank)."""
    axes = ("embed", "mlp")[:ndim] if ndim <= 2 else (None,) * ndim
    return named_sharding(mesh, axes, serving_rules())


def shard_map(f, *, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication (vma) check off: every call
    site in this repo has a Pallas call or collectives in its body, and
    those defeat the checker."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# Resolution + annotation
# ---------------------------------------------------------------------------

def to_pspec(axes: Sequence[Optional[str]],
             rules: Optional[Dict[str, AxisVal]] = None) -> PartitionSpec:
    """Logical axes tuple -> PartitionSpec under the active rules."""
    rules = rules if rules is not None else (_current() or {})
    parts = []
    used: set = set()
    for name in axes:
        val = rules.get(name) if name is not None else None
        # one mesh axis may appear only once in a spec
        if val is None:
            parts.append(None)
            continue
        vals = (val,) if isinstance(val, str) else tuple(val)
        vals = tuple(v for v in vals if v not in used)
        used.update(vals)
        if not vals:
            parts.append(None)
        elif len(vals) == 1:
            parts.append(vals[0])
        else:
            parts.append(vals)
    # trim trailing Nones for tidiness
    while parts and parts[-1] is None:
        parts.pop()
    return PartitionSpec(*parts)


def lshard(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """Constrain ``x``'s sharding by logical axes; no-op without rules."""
    rules = _current()
    if rules is None:
        return x
    assert len(axes) == x.ndim, f"{axes} vs rank {x.ndim}"
    spec = to_pspec(axes, rules)
    mesh = current_mesh()
    if mesh is not None:
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    return jax.lax.with_sharding_constraint(x, spec)


def named_sharding(mesh: Mesh, axes: Sequence[Optional[str]],
                   rules: Dict[str, AxisVal]) -> NamedSharding:
    return NamedSharding(mesh, to_pspec(axes, rules))


def tree_pspecs(axes_tree, rules: Dict[str, AxisVal]):
    """Map a pytree of logical-axes tuples to PartitionSpecs."""
    return jax.tree.map(
        lambda axes: to_pspec(axes, rules), axes_tree,
        is_leaf=lambda v: isinstance(v, tuple) and all(
            a is None or isinstance(a, str) for a in v),
    )


def tree_shardings(mesh: Mesh, axes_tree, rules: Dict[str, AxisVal]):
    return jax.tree.map(lambda spec: NamedSharding(mesh, spec),
                        tree_pspecs(axes_tree, rules),
                        is_leaf=lambda v: isinstance(v, PartitionSpec))
