"""Device telemetry simulator: the CPU/MEM/BATT/energy signals that feed
FedFog's health scoring (Eq. 1) and selection (Eq. 3).

AR(1) fluctuations for cpu/mem (load transients), battery that drains with
participation and trickle-charges otherwise, heterogeneous device classes
(wearable / camera / sensor, per the paper's §IV.A testbed description)
with different compute capacity (MIPS) and radio profiles.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.types import ClientTelemetry, _pytree_dataclass

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class TelemetryConfig:
    num_clients: int = 64
    ar_rho: float = 0.8  # AR(1) persistence for cpu/mem
    ar_noise: float = 0.12
    drain_per_round: float = 0.06  # battery drain when participating
    recharge: float = 0.01
    seed: int = 0


@_pytree_dataclass
class DeviceProfiles:
    """Static heterogeneity: (N,) arrays.

    Registered as a pytree so profiles can ride through jit/vmap/scan as
    explicit arguments of the scan-compiled simulator and the vmapped
    sweep subsystem (rather than leaking in as trace constants).
    """

    mips: Array  # compute capacity, instructions/s (sim units)
    bw_up: Array  # uplink bytes/s
    bw_down: Array  # downlink bytes/s
    rtt_ms: Array
    battery_capacity_j: Array


def make_profiles(cfg: TelemetryConfig) -> DeviceProfiles:
    key = jax.random.PRNGKey(cfg.seed + 30)
    ks = jax.random.split(key, 5)
    n = cfg.num_clients
    # device class mix: 0=wearable, 1=camera, 2=gateway-adjacent sensor
    cls = jax.random.randint(ks[0], (n,), 0, 3)
    mips = jnp.take(jnp.array([500e6, 1200e6, 800e6]), cls) * (
        1.0 + 0.3 * jax.random.normal(ks[1], (n,))
    )
    bw_up = jnp.take(jnp.array([1e6, 5e6, 2e6]), cls) * jnp.exp(
        0.3 * jax.random.normal(ks[2], (n,))
    )
    rtt = jnp.take(jnp.array([40.0, 15.0, 25.0]), cls) * jnp.exp(
        0.2 * jax.random.normal(ks[3], (n,))
    )
    cap = jnp.take(jnp.array([8e3, 40e3, 15e3]), cls)
    return DeviceProfiles(
        mips=jnp.abs(mips) + 1e5,
        bw_up=bw_up,
        bw_down=bw_up * 4,
        rtt_ms=rtt,
        battery_capacity_j=cap,
    )


def init_telemetry(cfg: TelemetryConfig) -> ClientTelemetry:
    key = jax.random.PRNGKey(cfg.seed + 31)
    ks = jax.random.split(key, 4)
    n = cfg.num_clients
    u = lambda k, lo, hi: jax.random.uniform(k, (n,), minval=lo, maxval=hi)
    batt = u(ks[2], 0.4, 1.0)
    return ClientTelemetry(
        cpu=u(ks[0], 0.4, 1.0),
        mem=u(ks[1], 0.4, 1.0),
        batt=batt,
        # Normalized energy level tracks battery. Its own buffer: the
        # engines donate the telemetry carry, and one buffer may not be
        # donated twice.
        energy=batt.copy(),
    )


def step_telemetry(
    cfg: TelemetryConfig,
    tel: ClientTelemetry,
    participated: Array,  # (N,) bool
    round_energy_j: Array,  # (N,)
    profiles: DeviceProfiles,
    key: Array,
) -> ClientTelemetry:
    k1, k2 = jax.random.split(key)
    n = cfg.num_clients

    def ar(x, k):
        noise = jax.random.normal(k, (n,)) * cfg.ar_noise
        mean = 0.7
        return jnp.clip(mean + cfg.ar_rho * (x - mean) + noise, 0.05, 1.0)

    batt = jnp.clip(
        tel.batt
        - participated * cfg.drain_per_round
        - round_energy_j / profiles.battery_capacity_j
        + (~participated) * cfg.recharge,
        0.0,
        1.0,
    )
    return ClientTelemetry(
        cpu=ar(tel.cpu, k1), mem=ar(tel.mem, k2), batt=batt, energy=batt
    )
