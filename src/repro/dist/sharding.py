"""Logical-axis -> mesh sharding rules (GSPMD partition specs).

Every parameter records a tuple of *logical* axis names at init time
(``PFac.param``); this module maps those names onto physical mesh axes.
``make_rules`` applies the per-arch divisibility fallbacks:

  heads   -> "model" when num_heads divides the model-axis size, else the
             qkv INPUT dim ("qkv_in") takes the shard (minicpm3's 40 heads)
  vocab   -> "model" when vocab_size divides, else the embedding shards on
             d_model ("embed") instead (minicpm3's 73448-row table)
  expert  -> "model" for expert-parallel MoE (deepseek-v2: 160/16); archs
             whose expert count cannot divide (grok-1: 8 experts) fall back
             to expert tensor-parallel over "moe_ff"

``logical_to_spec`` turns one axes-tuple into a ``PartitionSpec``, never
reusing a mesh axis within a single spec (first dim wins).  ``shard_batch``
is the activation-side constraint used by model forwards; it is a no-op
when no mesh is active (CPU tests) or when none of the requested batch axes
exist on the current mesh.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

Rules = Dict[str, Optional[str]]

#: every logical axis name recorded by PFac across the model zoo
LOGICAL_AXES = ("embed", "vocab", "heads", "kv", "qkv_in", "attn_out",
                "mlp", "moe_ff", "expert")


def _axis_size(mesh, name: str) -> int:
    return int(getattr(mesh, "shape", {}).get(name, 1))


def make_rules(cfg, mesh, *, no_tp: bool = False) -> Rules:
    """Map logical axis names -> mesh axis name (or None = replicate)."""
    rules: Rules = {name: None for name in LOGICAL_AXES}
    m = _axis_size(mesh, "model")
    if no_tp or m <= 1:
        return rules

    # attention: shard heads when divisible, else shard the qkv input dim
    if cfg.num_heads % m == 0:
        rules["heads"] = "model"
    elif cfg.d_model % m == 0:
        rules["qkv_in"] = "model"
    if cfg.num_kv_heads and cfg.num_kv_heads % m == 0:
        rules["kv"] = "model"

    # embedding/head: vocab shard when divisible, else d_model shard
    if cfg.vocab_size % m == 0:
        rules["vocab"] = "model"
    elif cfg.d_model % m == 0:
        rules["embed"] = "model"

    # dense MLP hidden
    if cfg.d_ff and cfg.d_ff % m == 0:
        rules["mlp"] = "model"

    # MoE: expert-parallel when the expert count divides, else expert-TP
    if getattr(cfg, "num_experts", 0):
        if cfg.moe_sharding == "ep" and cfg.num_experts % m == 0:
            rules["expert"] = "model"
        elif cfg.moe_d_ff % m == 0:
            rules["moe_ff"] = "model"
    return rules


def logical_to_spec(axes: Tuple[Optional[str], ...], rules: Rules,
                    shape: Optional[Tuple[int, ...]] = None) -> P:
    """PartitionSpec for one leaf. A mesh axis is used at most once per spec
    (the first logical dim mapping to it wins; later dims replicate).

    ``shape`` is accepted for signature stability but intentionally unused:
    all divisibility decisions are resolved ONCE per arch in ``make_rules``
    (which knows the mesh axis sizes); per-leaf spec construction is purely
    name-based."""
    used = set()
    out = []
    for i, name in enumerate(axes):
        mesh_axis = rules.get(name) if name is not None else None
        if mesh_axis is not None and mesh_axis in used:
            mesh_axis = None
        if mesh_axis is not None:
            used.add(mesh_axis)
        out.append(mesh_axis)
    return P(*out)


# ---------------------------------------------------------------------------
# Tree-level shardings (dry-run / launcher)
# ---------------------------------------------------------------------------


def tree_shardings(mesh, axes_tree, rules: Rules, aparams):
    """TP-only NamedShardings mirroring the param tree."""
    is_axes_leaf = lambda x: isinstance(x, tuple) and all(
        a is None or isinstance(a, str) for a in x)

    def one(axes, leaf):
        return NamedSharding(mesh, logical_to_spec(axes, rules, leaf.shape))

    return jax.tree.map(one, axes_tree, aparams, is_leaf=is_axes_leaf)


def fsdp_tree_shardings(mesh, axes_tree, rules: Rules, aparams, *,
                        fsdp_axes: Tuple[str, ...] = ("data",),
                        output_dim_only: bool = False):
    """TP specs plus FSDP: shard the largest still-replicated dim of each
    leaf over ``fsdp_axes`` when divisible. ``output_dim_only`` restricts
    FSDP to the last (output) dim — avoids sharding contracting dims."""
    fsdp = tuple(a for a in fsdp_axes if _axis_size(mesh, a) > 1)
    n_fsdp = int(np.prod([_axis_size(mesh, a) for a in fsdp])) if fsdp else 1
    is_axes_leaf = lambda x: isinstance(x, tuple) and all(
        a is None or isinstance(a, str) for a in x)

    def one(axes, leaf):
        spec = list(logical_to_spec(axes, rules, leaf.shape))
        spec += [None] * (len(leaf.shape) - len(spec))
        if fsdp and n_fsdp > 1:
            cands = range(len(leaf.shape) - 1, len(leaf.shape)) \
                if output_dim_only else range(len(leaf.shape))
            best = None
            for d in cands:
                if spec[d] is None and leaf.shape[d] % n_fsdp == 0 \
                        and leaf.shape[d] >= n_fsdp:
                    if best is None or leaf.shape[d] > leaf.shape[best]:
                        best = d
            if best is not None:
                spec[best] = fsdp if len(fsdp) > 1 else fsdp[0]
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(one, axes_tree, aparams, is_leaf=is_axes_leaf)


def batch_spec(mesh, nd: int) -> NamedSharding:
    """Leading-dim data-parallel sharding over whatever dp axes exist."""
    dp = tuple(a for a in ("pod", "data") if _axis_size(mesh, a) > 1)
    lead = dp if len(dp) > 1 else (dp[0] if dp else None)
    return NamedSharding(mesh, P(*((lead,) + (None,) * (nd - 1))))


# ---------------------------------------------------------------------------
# Client-axis (federated cohort) sharding — fl/engine.py's shard_map path
# ---------------------------------------------------------------------------

#: mesh axis name for the federated cohort dimension (launch/mesh.py's
#: ``make_client_mesh``); the fused round engine shard_maps over it
CLIENT_AXIS = "clients"


def client_axis_size(mesh) -> int:
    """Size of the cohort axis on ``mesh`` (1 when absent or no mesh)."""
    return 1 if mesh is None else _axis_size(mesh, CLIENT_AXIS)


def client_spec(nd: int) -> P:
    """PartitionSpec sharding the leading (client) dim of an ``nd``-rank
    array, everything else replicated."""
    return P(*((CLIENT_AXIS,) + (None,) * (nd - 1)))


def shard_cohort(mesh, tree):
    """device_put a stacked-cohort pytree (leading dim = clients, already
    padded by the caller to a multiple of the client-axis size) partitioned
    along the client axis."""
    return jax.tree.map(
        lambda x: jax.device_put(
            x, NamedSharding(mesh, client_spec(np.ndim(x)))), tree)


def replicate(mesh, tree):
    """device_put a pytree fully replicated over ``mesh`` (round-start
    params / frozen prefix / BN state in the sharded round)."""
    return jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(mesh, P())), tree)


def shard_client_arrays(mesh, tree):
    """Place per-client [N, ...] arrays (``ClientPopulation`` columns,
    ``FleetTimeModel`` columns, error-feedback pools) along the client axis.

    Same divisibility discipline as ``make_rules``: a leaf whose leading dim
    does not divide the client-axis size is REPLICATED instead of sharded —
    still correct, just not distributed. Identity when no client axis is
    active (CPU tests, single device)."""
    m = client_axis_size(mesh)
    if m <= 1:
        return tree

    def put(x):
        nd = np.ndim(x)
        if nd >= 1 and np.shape(x)[0] % m == 0:
            return jax.device_put(x, NamedSharding(mesh, client_spec(nd)))
        return jax.device_put(x, NamedSharding(mesh, P()))

    return jax.tree.map(put, tree)


# ---------------------------------------------------------------------------
# Activation-side constraint
# ---------------------------------------------------------------------------


def _current_mesh():
    """The mesh installed by ``with mesh:``, or None."""
    from jax._src.mesh import thread_resources
    m = thread_resources.env.physical_mesh
    return None if m.empty else m


def shard_batch(x, *, batch_axes: Tuple[str, ...] = ("pod", "data")):
    """Constrain an activation's leading (batch) dim over the dp axes of the
    active mesh. Identity on CPU tests / whenever no mesh is installed."""
    mesh = _current_mesh()
    if mesh is None:
        return x
    axes = tuple(a for a in batch_axes if _axis_size(mesh, a) > 1)
    if not axes or x.ndim == 0 or x.shape[0] % int(
            np.prod([_axis_size(mesh, a) for a in axes])) != 0:
        return x
    lead = axes if len(axes) > 1 else axes[0]
    spec = P(*((lead,) + (None,) * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
