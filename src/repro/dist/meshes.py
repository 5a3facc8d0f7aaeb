"""Mesh plans: how a device pool factorizes into FedFog's parallel axes.

The pod-scale round (fl/round.py) distributes over FOUR kinds of axes:

    pod      inter-pod replica axis (multi-pod only; size 2)
    client   concurrent FL cohort slots — the stacked per-slot replicas of
             the global model live here; Eq. 6's aggregation is the ONE
             collective that crosses it
    zero     intra-slot data/ZeRO axis — each slot's local batch and (with
             ``fsdp_params``) its parameters/moments shard here
    model    two tensor axes: ("expert","tp") for MoE archs,
             ("tp","sp") otherwise

A :class:`MeshPlan` is pure arithmetic — importing this module never
touches jax device state; :meth:`MeshPlan.build_mesh` is the only call
that does. The production contract (launch/mesh.py) is 256 chips/pod as
16 data × 16 model; ``plan_for`` refines that into the axes above with
per-arch divisibility (expert count, head count) and supports scaled-down
``device_count`` plans for CPU hosts backed by XLA's fake devices.
"""
from __future__ import annotations

import dataclasses
import math

from repro.models.config import ModelConfig

# Production contract (launch/mesh.py): per-pod data × model factorization.
DATA_PER_POD = 16
MODEL_PER_POD = 16
DEFAULT_ZERO = 2


def _largest_divisor(budget: int, dim: int) -> int:
    """Largest divisor of ``budget`` that also divides ``dim``."""
    for c in sorted((d for d in range(1, budget + 1) if budget % d == 0),
                    reverse=True):
        if dim % c == 0:
            return c
    return 1


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """Axis factorization of one training/serving device pool.

    ``num_clients`` is the TOTAL slot count across pods (the stacked
    leading dim of per-slot params); per-pod it is ``num_clients //
    num_pods``. Invariants (asserted in tests/test_sharding_rules.py):

        num_clients * zero == num_pods * DATA_PER_POD   (production plans)
        model_split[0] * model_split[1] == MODEL_PER_POD
        num_experts % model_split[0] == 0               (MoE archs)
        num_heads   % model_split[0] == 0               (dense archs, tp>1)
    """

    num_pods: int
    num_clients: int  # total across pods
    zero: int
    model_axes: tuple[str, str]
    model_split: tuple[int, int]
    fsdp_params: bool = True

    # ------------------------------------------------------------------ #
    @property
    def multi_pod(self) -> bool:
        return self.num_pods > 1

    @property
    def client_axes(self) -> tuple[str, ...]:
        """Mesh axes the stacked slot dim shards over."""
        return ("pod", "client") if self.multi_pod else ("client",)

    @property
    def data_axes(self) -> tuple[str, ...]:
        """Mesh axes a serving batch dim shards over (all non-model axes)."""
        return self.client_axes + ("zero",)

    @property
    def axis_names(self) -> tuple[str, ...]:
        base = ("pod",) if self.multi_pod else ()
        return base + ("client", "zero") + self.model_axes

    @property
    def axis_sizes(self) -> tuple[int, ...]:
        base = (self.num_pods,) if self.multi_pod else ()
        return base + (
            self.num_clients // self.num_pods,
            self.zero,
        ) + self.model_split

    @property
    def shape(self) -> dict[str, int]:
        return dict(zip(self.axis_names, self.axis_sizes))

    @property
    def device_count(self) -> int:
        return math.prod(self.axis_sizes)

    # ------------------------------------------------------------------ #
    def build_mesh(self, devices=None):
        """Materialize the plan as a jax Mesh (first ``device_count``
        local devices unless an explicit device array is given).

        Every axis is ``Auto``: the round pins layouts with
        ``with_sharding_constraint``, which only accepts Auto axes, and
        ``jax.make_mesh`` defaults to Explicit axes."""
        import jax
        import numpy as np

        auto = (jax.sharding.AxisType.Auto,) * len(self.axis_names)
        if devices is None:
            return jax.make_mesh(
                self.axis_sizes, self.axis_names, axis_types=auto
            )
        devs = np.asarray(devices).reshape(self.axis_sizes)
        return jax.sharding.Mesh(devs, self.axis_names, axis_types=auto)


def plan_for(
    cfg: ModelConfig,
    *,
    multi_pod: bool = False,
    device_count: int | None = None,
    zero: int | None = None,
) -> MeshPlan:
    """Compute the per-arch mesh plan.

    Default (``device_count=None``) is the production pool: 256 chips per
    pod as (client·zero=16) × (model=16), doubled along a leading ``pod``
    axis when ``multi_pod``. An explicit ``device_count`` builds a scaled
    host plan with NO model parallelism (client·zero = device_count) —
    the shape used by fake-device CPU runs and the 8-device integration
    test.

    Model-axis factorization:
      * MoE archs: ``("expert", "tp")`` with the expert axis the largest
        16-divisor of ``num_experts`` (moonshot 64→16·1, mixtral 8→8·2).
      * Everything else: ``("tp", "sp")`` with tp the largest 16-divisor
        of the head count (rwkv6's heads are ``d_model//64``); archs whose
        head count resists 2-powers (hymba's 25) get tp=1 and lean on the
        ``sp`` axis for ffn/vocab/state dims.
    """
    num_pods = 2 if multi_pod else 1

    if device_count is None:
        data_per_pod = DATA_PER_POD
        model_total = MODEL_PER_POD
    else:
        if device_count % num_pods:
            raise ValueError(
                f"device_count {device_count} not divisible by {num_pods} pods"
            )
        data_per_pod = device_count // num_pods
        model_total = 1  # scaled host plans skip tensor parallelism

    z = zero if zero is not None else (
        DEFAULT_ZERO if data_per_pod % DEFAULT_ZERO == 0 else 1
    )
    if data_per_pod % z:
        raise ValueError(f"zero={z} does not divide data axis {data_per_pod}")
    clients_per_pod = data_per_pod // z

    if cfg.num_experts:
        e = _largest_divisor(model_total, cfg.num_experts)
        model_axes, model_split = ("expert", "tp"), (e, model_total // e)
    else:
        # rwkv6 has no attention heads; its head-sharded dims are d_model
        # in units of the fixed 64-wide rwkv head.
        heads = cfg.num_heads or max(cfg.d_model // 64, 1)
        t = _largest_divisor(model_total, heads)
        model_axes, model_split = ("tp", "sp"), (t, model_total // t)

    return MeshPlan(
        num_pods=num_pods,
        num_clients=clients_per_pod * num_pods,
        zero=z,
        model_axes=model_axes,
        model_split=model_split,
    )
