"""Logical-axis -> mesh-axis sharding rules (MaxText-style).

Params are annotated with logical names at init ("embed", "mlp", "heads",
"vocab", "expert", "layers", None); this module maps them to the production
mesh: tensor-parallel dims go to "model", FSDP dims to "data". Divisibility
is checked per array — a logical rule silently degrades to replication when
the dim does not divide the axis (e.g. 8 kv-heads on a 16-way model axis),
which keeps every (arch x shape x mesh) cell compilable.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.launch.mesh import axis_size, batch_axes

# logical name -> preferred mesh axes, in priority order
DEFAULT_RULES: dict = {
    "vocab": ("model",),
    "heads": ("model",),
    "mlp": ("model",),
    "expert": ("model",),
    "embed": ("data",),  # FSDP: shard the big replicated dim over data
    "layers": (),  # scanned over, never sharded
    None: (),
}


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    rules: dict = dataclasses.field(default_factory=lambda: dict(DEFAULT_RULES))
    fsdp: bool = True

    def mesh_axes_for(self, logical: Optional[str]) -> tuple[str, ...]:
        axes = self.rules.get(logical, ())
        if not self.fsdp:
            axes = tuple(a for a in axes if a != "data")
        return axes

    def spec_for(self, shape: tuple, axes: tuple, mesh: Mesh) -> P:
        """PartitionSpec for one param, enforcing divisibility."""
        used: set = set()
        entries = []
        for dim, logical in zip(shape, axes):
            chosen = None
            for cand in self.mesh_axes_for(logical):
                if cand in used or cand not in mesh.axis_names:
                    continue
                if dim % mesh.shape[cand] == 0:
                    chosen = cand
                    used.add(cand)
                    break
            entries.append(chosen)
        return P(*entries)


def param_shardings(specs, mesh: Mesh, rules: ShardingRules,
                    shapes) -> Any:
    """specs: logical-axes pytree; shapes: matching ShapeDtypeStruct/array
    pytree. Returns NamedSharding pytree."""
    is_axes = lambda x: isinstance(x, tuple)
    return jax.tree.map(
        lambda ax, arr: NamedSharding(
            mesh, rules.spec_for(arr.shape, ax, mesh)),
        specs,
        shapes,
        is_leaf=is_axes,
    )


# ----------------------------------------------- paged serving pool --------
def paged_pool_pspec() -> P:
    """PartitionSpec for paged-pool QuantizedKV leaves.

    Every pool leaf is rank 5 — (L, num_pages, n_kv, page_size, X) where X
    is the packed trailing dim (index words / norm codes / range scalars) —
    so one spec covers the whole tree: shard the kv-head axis over "model",
    replicate everything else. The trailing dims are implicitly replicated
    (a PartitionSpec is a prefix)."""
    return P(None, None, "model")


def kv_shard_count(cfg, mesh: Mesh) -> int:
    """Model-axis size for sharded paged serving, with divisibility checks.

    Unlike `spec_for`'s silent degrade-to-replication (right for weights),
    the paged pool REQUIRES the head split — a non-divisible config is a
    deployment error, not something to paper over."""
    if "model" not in mesh.axis_names:
        raise ValueError(f"mesh {mesh.axis_names} has no 'model' axis")
    n = int(mesh.shape["model"])
    if cfg.num_kv_heads % n != 0 or cfg.num_heads % n != 0:
        raise ValueError(
            f"cannot shard {cfg.num_kv_heads} kv-heads / {cfg.num_heads} "
            f"q-heads over a {n}-way model axis")
    return n


def shard_paged_pool(tree, mesh: Mesh):
    """Commit a QuantizedKV pool tree (or any rank-5 pool leaves) to the
    kv-head sharding. Re-applied after restore/migrate so pressure-path
    scatters never silently drop the layout."""
    sh = NamedSharding(mesh, paged_pool_pspec())
    return jax.tree.map(lambda a: jax.device_put(a, sh), tree)


def replicate(tree, mesh: Mesh):
    """Commit a pytree (params, tables) to full replication over the mesh."""
    sh = NamedSharding(mesh, P())
    return jax.tree.map(lambda a: jax.device_put(a, sh), tree)


# ----------------------------------------------------- data shardings ------
def batch_spec(mesh: Mesh, global_batch: int) -> P:
    """Shard the batch dim over (pod, data) when divisible."""
    ba = batch_axes(mesh)
    if ba and global_batch % axis_size(mesh, *ba) == 0:
        return P(ba)
    return P(None)


def batch_shardings(mesh: Mesh, batch_tree, *, seq_axis_model: bool = False
                    ) -> Any:
    """Sharding for an input batch dict: dim0 = batch, rest replicated
    (optionally seq over 'model' for sequence-parallel inputs)."""

    def one(arr):
        b = arr.shape[0]
        bs = batch_spec(mesh, b)
        entries = list(bs) + [None] * (len(arr.shape) - 1)
        if seq_axis_model and len(arr.shape) >= 2 and "model" in mesh.axis_names:
            if arr.shape[1] % mesh.shape["model"] == 0:
                entries[1] = "model"
        return NamedSharding(mesh, P(*entries))

    return jax.tree.map(one, batch_tree)


def cache_sharding(mesh: Mesh, cfg, lead_shape: tuple) -> NamedSharding:
    """Sharding for KV-cache arrays shaped (L, B, T, H, ...).

    Serving layout ("TP-serve"): weights stay 2D-sharded (data x model), so
    the batch must NOT shard over "data" (that would force a per-token FSDP
    weight re-gather — 47 GB/chip/step at 405B, §Perf iteration). Instead:
    batch -> "pod" (weights are pod-replicated), tokens -> "data"
    (sequence-parallel cache; GSPMD inserts the partial-softmax combine),
    kv-heads -> "model" when divisible.
    """
    if len(lead_shape) < 4:  # scalars (length counters) etc.
        return NamedSharding(mesh, P())
    l, b, t, h = lead_shape[:4]
    extra = len(lead_shape) - 4
    b_axes = ("pod",) if ("pod" in mesh.axis_names
                          and b % mesh.shape["pod"] == 0) else ()
    t_axes: list = []
    h_axes: tuple = ()
    if "data" in mesh.axis_names and t % mesh.shape["data"] == 0:
        t_axes.append("data")
    if "model" in mesh.axis_names and h % mesh.shape["model"] == 0:
        h_axes = ("model",)
    elif "model" in mesh.axis_names and t % mesh.shape["model"] == 0:
        t_axes.append("model")
    return NamedSharding(
        mesh,
        P(None, b_axes or None, tuple(t_axes) or None, h_axes or None,
          *([None] * extra)),
    )


def state_sharding(mesh: Mesh, arr_shape: tuple, batch_dim: int = 1
                   ) -> NamedSharding:
    """Recurrent-state arrays (groups, per, B, H, ...) — shard B, then H."""
    entries: list = [None] * len(arr_shape)
    ba = batch_axes(mesh)
    if ba and arr_shape[batch_dim] % axis_size(mesh, *ba) == 0:
        entries[batch_dim] = ba
    if "model" in mesh.axis_names and len(arr_shape) > batch_dim + 1:
        if arr_shape[batch_dim + 1] % mesh.shape["model"] == 0:
            entries[batch_dim + 1] = "model"
    return NamedSharding(mesh, P(*entries))


def activation_constraint(mesh: Mesh, *, seq_parallel: bool):
    """Kind-aware with_sharding_constraint for activations.

    kinds:
      residual   (B,S,D)   — batch over (pod,data); S over model if SP.
                             Megatron-SP: GSPMD inserts the S all-gather
                             before attention/MLP, reduce-scatter after.
      ffn_hidden (B,S,F)   — F over model (Megatron TP). Without this anchor
                             GSPMD keeps hiddens seq-sharded and the weight
                             GRADS become full-size unsharded partials
                             (3.25 GiB f32 per MLP matrix at 405B).
      heads      (B,S,N,H) — attention heads over model.
      moe_buf    (E,C,D)   — expert dim over model (EP).
    """
    ba = batch_axes(mesh)
    msz = mesh.shape.get("model", 1) if "model" in mesh.axis_names else 1

    def _b(dim):  # batch entry with divisibility guard
        return ba if (ba and dim % max(axis_size(mesh, *ba), 1) == 0) else None

    def _m(dim):  # model entry with divisibility guard
        return "model" if ("model" in mesh.axis_names and dim % msz == 0
                           and dim >= msz) else None

    def _groups_entry(g_dim):
        all_ax = ba + (("model",) if "model" in mesh.axis_names else ())
        if all_ax and g_dim % axis_size(mesh, *all_ax) == 0:
            return all_ax
        if ba and g_dim % max(axis_size(mesh, *ba), 1) == 0:
            return ba
        return None

    def constrain(x, kind: str = "residual"):
        if kind == "residual" and x.ndim == 3:
            entries = [_b(x.shape[0]),
                       _m(x.shape[1]) if seq_parallel else None, None]
        elif kind == "ffn_hidden" and x.ndim == 3:
            entries = [_b(x.shape[0]), None, _m(x.shape[2])]
        elif kind == "heads" and x.ndim == 4:
            entries = [_b(x.shape[0]), None, _m(x.shape[2]), None]
        elif kind == "moe_buf" and x.ndim == 4:
            # (G, E, C, D): groups over batch axes (+model when G covers the
            # whole mesh — small-expert configs replicate weights instead),
            # experts over model otherwise
            g_ent = _groups_entry(x.shape[0])
            e_ent = _m(x.shape[1]) if (g_ent is None or
                                       "model" not in g_ent) else None
            entries = [g_ent, e_ent, None, None]
        elif kind == "moe_tokens" and x.ndim == 3:
            # (G, t_g, D): groups over batch axes (+model when divisible)
            entries = [_groups_entry(x.shape[0]), None, None]
        elif kind == "moe_buf" and x.ndim == 3:
            entries = [_m(x.shape[0]), None, None]
        elif kind == "logits" and x.ndim == 3:
            # (B, S, V): vocab over model. Without this the SP seq-sharding
            # propagates into the logits and the lm_head matmul gathers the
            # full (d_model, vocab) matrix per device (7.8 GiB f32 at 405B).
            entries = [_b(x.shape[0]), None, _m(x.shape[2])]
        else:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(*entries)))

    return constrain
