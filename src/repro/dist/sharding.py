"""Sharding rules: logical param/batch/cache axes → mesh PartitionSpecs.

The models declare LOGICAL axes per parameter dim (``ParamDecl.axes`` —
"embed", "heads", "mlp", "experts", …). :class:`ShardingRules` maps those
onto the :class:`~repro.dist.meshes.MeshPlan` mesh axes with a rule table
plus a divisibility guard: an axis is only taken when its size divides the
dim (GQA kv heads smaller than tp, hymba's 25 heads, etc. fall back to
replication instead of failing to lower).

Rule table (production plans; size-1 axes drop out automatically):

    embed       zero            (param FSDP — off when ``plan.fsdp_params``
                                 is False or ``fsdp=False`` for serving)
    heads/kv    tp
    head_dim    sp
    mlp/vocab/ssm   tp, sp      (joint — the big ffn/vocab dims absorb the
                                 full 16-way model split)
    experts     expert
    expert_mlp  tp
    layers / None   replicated  (layers is the scan-carried stack dim)

Stacked FL params (``stacked=True``) prepend the slot axis sharded over
``plan.client_axes`` — the layout whose aggregation is the round's ONE
inter-client all-reduce. Batch specs shard the batch dim over all data
axes; decode caches fall back to SEQUENCE-parallel sharding when the
batch dim is unshardable (the long_500k cells with global_batch=1).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Mapping

from jax.sharding import PartitionSpec as P

from repro.dist.meshes import MeshPlan, plan_for
from repro.models.config import ModelConfig

# Logical axis -> ordered mesh-axis candidates. Axes are taken greedily
# left-to-right while the running product divides the dim.
LOGICAL_RULES: dict[str, tuple[str, ...]] = {
    "layers": (),
    "embed": ("zero",),  # FSDP; dropped when fsdp is off
    "heads": ("tp",),
    "kv": ("tp",),
    "head_dim": ("sp",),
    "mlp": ("tp", "sp"),
    "vocab": ("tp", "sp"),
    "ssm": ("tp", "sp"),
    "experts": ("expert",),
    "expert_mlp": ("tp",),
}


def _flat_with_axes(shapes, laxes):
    """Zip a ShapeDtypeStruct tree with its logical-axes tree.

    ``axes_tree`` leaves are tuples (which jax.tree would descend into),
    so both trees are flattened explicitly with matching is_leaf guards.
    """
    import jax

    flat_s, treedef = jax.tree.flatten(shapes)
    flat_a, _ = jax.tree.flatten(
        laxes, is_leaf=lambda x: isinstance(x, tuple)
    )
    assert len(flat_s) == len(flat_a), (len(flat_s), len(flat_a))
    return flat_s, flat_a, treedef


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    cfg: ModelConfig
    plan: MeshPlan
    mesh: Any  # jax.sharding.Mesh (or anything exposing .shape: dict)

    # ------------------------------------------------------------------ #
    # Axis helpers
    # ------------------------------------------------------------------ #
    def _axis_size(self, name: str) -> int:
        return int(self.mesh.shape.get(name, 1))

    def _present(self, axes) -> tuple[str, ...]:
        return tuple(a for a in axes if self._axis_size(a) > 1)

    def _as_spec_entry(self, axes):
        """Mesh-axis tuple -> PartitionSpec entry (size-1 axes dropped)."""
        axes = self._present(axes)
        if not axes:
            return None
        return axes[0] if len(axes) == 1 else axes

    def _take_axes(self, candidates, dim: int, used: set[str]):
        """Greedy divisible prefix of ``candidates`` for a dim of extent
        ``dim``; each mesh axis is used at most once per spec."""
        chosen: list[str] = []
        prod = 1
        for a in candidates:
            size = self._axis_size(a)
            if size <= 1 or a in used:
                continue
            if dim % (prod * size):
                continue
            chosen.append(a)
            prod *= size
        used.update(chosen)
        if not chosen:
            return None
        return chosen[0] if len(chosen) == 1 else tuple(chosen)

    @property
    def batch_axes(self) -> tuple[str, ...]:
        """Intra-slot data axes — how a per-slot batch shards inside the
        client vmap of the FL round."""
        return self._present(("zero",))

    @property
    def client_ways(self) -> int:
        """Total mesh extent the client/slot axis is sharded over."""
        prod = 1
        for a in self._present(self.plan.client_axes):
            prod *= self._axis_size(a)
        return prod

    def fused_delta_spec(self, p_total: int | None = None, *,
                         shard_p: bool = True):
        """PartitionSpec for the fused (C, P) client-delta buffer: the
        client dim over the plan's client axes, the P dim over zero when
        it divides (the reference one-all-reduce aggregation layout).
        ``shard_p=False`` keeps P whole per client shard — the layout
        the sharded delta-pipeline kernel consumes (each shard needs its
        clients' full rows for exact clip norms / compression tables)."""
        from jax.sharding import PartitionSpec as P

        z = "zero" if shard_p and self._axis_size("zero") > 1 else None
        if z is not None and p_total is not None and p_total % self._axis_size("zero"):
            z = None
        return P(self._as_spec_entry(self.plan.client_axes), z)

    def fused_delta_sharding(self, p_total: int | None = None, *,
                             shard_p: bool = True):
        from jax.sharding import NamedSharding

        return NamedSharding(
            self.mesh, self.fused_delta_spec(p_total, shard_p=shard_p)
        )

    @property
    def serve_batch_axes(self) -> tuple[str, ...]:
        """All data axes — how a serving batch dim shards (no slot stack)."""
        return self._present(self.plan.data_axes)

    # ------------------------------------------------------------------ #
    # Parameters / optimizer state
    # ------------------------------------------------------------------ #
    def param_specs(self, shapes, laxes, *, stacked: bool = False,
                    fsdp: bool | None = None):
        """PartitionSpec tree for a param tree.

        ``stacked=True`` prepends the per-slot replica axis (sharded over
        ``plan.client_axes``) — the FL round's in-flight layout.
        ``fsdp`` overrides ``plan.fsdp_params`` (serving passes False: no
        ZeRO sharding of weights on the decode path).
        """
        import jax

        use_fsdp = self.plan.fsdp_params if fsdp is None else fsdp
        client_entry = (
            self._as_spec_entry(self.plan.client_axes) if stacked else None
        )
        flat_s, flat_a, treedef = _flat_with_axes(shapes, laxes)

        specs = []
        for sds, axes in zip(flat_s, flat_a):
            assert len(axes) == len(sds.shape), (axes, sds.shape)
            used: set[str] = set(self.plan.client_axes) if stacked else set()
            entries = []
            for dim, name in zip(sds.shape, axes):
                rule = LOGICAL_RULES.get(name, ()) if name else ()
                if not use_fsdp:
                    rule = tuple(a for a in rule if a != "zero")
                entries.append(self._take_axes(rule, dim, used))
            if stacked:
                entries = [client_entry] + entries
            specs.append(P(*entries))
        return jax.tree.unflatten(treedef, specs)

    def opt_spec_tree(self, shapes, laxes, *, stacked: bool = False):
        """Specs for one optimizer-moment tree (mirrors the params: ZeRO
        moments shard exactly like the weights they track)."""
        return self.param_specs(shapes, laxes, stacked=stacked, fsdp=True)

    # ------------------------------------------------------------------ #
    # Batches
    # ------------------------------------------------------------------ #
    def _data_prod(self) -> int:
        prod = 1
        for a in self.serve_batch_axes:
            prod *= self._axis_size(a)
        return prod

    def train_batch_specs(self, specs: Mapping[str, Any]) -> dict[str, P]:
        """Global (slot-major) train inputs: batch dim over ALL data axes
        (pod × client × zero); the round reshapes to (slots, per_slot) and
        re-pins with ``constrain_batch``."""
        entry = self._as_spec_entry(self.plan.data_axes)
        prod = self._data_prod()
        out = {}
        for k, sds in specs.items():
            dims = tuple(sds.shape)
            if entry is not None and dims and dims[0] % prod == 0:
                out[k] = P(entry, *([None] * (len(dims) - 1)))
            else:
                out[k] = P()
        return out

    def serve_batch_specs(self, specs: Mapping[str, Any]) -> dict[str, P]:
        """Serving inputs: batch dim over all data axes; batch-unshardable
        cells (long-context, global_batch=1) fall back to sharding the
        sequence dim (sequence-parallel prefill/decode)."""
        entry = self._as_spec_entry(self.plan.data_axes)
        prod = self._data_prod()
        out = {}
        for k, sds in specs.items():
            dims = tuple(sds.shape)
            if entry is None or not dims:
                out[k] = P()
            elif dims[0] % prod == 0:
                out[k] = P(entry, *([None] * (len(dims) - 1)))
            elif len(dims) >= 2 and dims[1] % prod == 0 and dims[1] >= prod:
                out[k] = P(None, entry, *([None] * (len(dims) - 2)))
            else:
                out[k] = P()
        return out

    # ------------------------------------------------------------------ #
    # Decode caches
    # ------------------------------------------------------------------ #
    def cache_specs(self, cache):
        """Specs for a decode-cache tree.

        Cache leaves are (layers, batch, ...) stacks: prefer sharding the
        batch dim (dim 1) over the data axes; when the batch is too small
        (long_500k's global_batch=1) shard the largest remaining dim —
        the sequence for KV caches (sequence-parallel decode), the state/
        feature dim for O(1)-state families (rwkv/ssm). The leading layer
        stack is never sharded.
        """
        import jax

        entry = self._as_spec_entry(self.plan.data_axes)
        prod = self._data_prod()

        def one(sds):
            dims = tuple(sds.shape)
            if entry is None or len(dims) < 3:
                return P()
            none = [None] * len(dims)
            if dims[1] % prod == 0 and dims[1] >= prod:
                none[1] = entry
                return P(*none)
            # largest shardable trailing dim, never dim 0 (layers)
            rest = sorted(range(2, len(dims)), key=lambda i: -dims[i])
            for i in rest:
                if dims[i] % prod == 0 and dims[i] >= prod:
                    none[i] = entry
                    return P(*none)
            return P()

        return jax.tree.map(one, cache)

    # ------------------------------------------------------------------ #
    # FL round wiring (shared by launch/train, launch/dryrun, selftest)
    # ------------------------------------------------------------------ #
    def fl_state_specs(self, model, state_abs):
        """PartitionSpec FLState for the round's carried state: params and
        server moments via the rule table, scheduler/rng scalars
        replicated. ``state_abs`` is an abstract (or concrete) FLState —
        only ``server_mu is None`` is read from it."""
        import jax

        from repro.fl.state import FLState

        shapes, laxes = model.param_shapes(), model.param_axes()
        rep = P()
        return FLState(
            params=self.param_specs(shapes, laxes, stacked=False),
            server_mu=(
                self.opt_spec_tree(shapes, laxes, stacked=False)
                if state_abs.server_mu is not None
                else None
            ),
            server_count=rep,
            sched=jax.tree.map(lambda _: rep, state_abs.sched),
            rng=rep,
            step=rep,
        )

    def fl_batch_shardings(self, batch):
        """NamedShardings for a round-batch dict: model inputs (tokens /
        patch_embeds / frames) over the data axes, the (N-client)
        scheduler inputs replicated."""
        from jax.sharding import NamedSharding

        model_in = {
            k: batch[k]
            for k in ("tokens", "patch_embeds", "frames")
            if k in batch
        }
        out = {
            k: NamedSharding(self.mesh, v)
            for k, v in self.train_batch_specs(model_in).items()
        }
        rep = self.replicated()
        for k in batch:
            out.setdefault(k, rep)
        return out

    # ------------------------------------------------------------------ #
    # NamedSharding constructors
    # ------------------------------------------------------------------ #
    def shardings(self, spec_tree):
        """PartitionSpec tree -> NamedSharding tree on this mesh."""
        import jax
        from jax.sharding import NamedSharding

        return jax.tree.map(
            lambda s: NamedSharding(self.mesh, s),
            spec_tree,
            is_leaf=lambda x: isinstance(x, P),
        )

    def replicated(self):
        from jax.sharding import NamedSharding

        return NamedSharding(self.mesh, P())


def make_rules(
    mesh,
    cfg: ModelConfig,
    *,
    multi_pod: bool = False,
    zero: int | None = None,
    device_count: int | None = None,
    devices=None,
) -> ShardingRules:
    """Build the plan + plan-shaped mesh + rules for one config.

    ``mesh`` may be the production (pod ×) data × model mesh from
    launch/mesh.py — its devices are re-laid-out onto the plan's axes —
    or None to lay the plan over ``devices`` (default: the first
    ``plan.device_count`` local devices).
    """
    plan = plan_for(
        cfg, multi_pod=multi_pod, device_count=device_count, zero=zero
    )
    if mesh is None:
        mesh = plan.build_mesh(devices)
    elif tuple(getattr(mesh, "axis_names", ())) != plan.axis_names:
        import numpy as np

        devs = np.asarray(mesh.devices)
        if devs.size != plan.device_count:
            raise ValueError(
                f"mesh has {devs.size} devices; plan needs {plan.device_count}"
            )
        mesh = plan.build_mesh(devs.reshape(plan.axis_sizes))
    return ShardingRules(cfg=cfg, plan=plan, mesh=mesh)
