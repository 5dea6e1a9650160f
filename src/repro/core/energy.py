"""Energy and network-topology models — the paper's stated future work,
implemented (§6: "power consumption, heat dissipation", "BRITE topology").

Power model (linear-in-utilization, the standard DVFS-era datacenter model):
    P(host) = P_idle + (P_peak - P_idle) * utilization
or, per host, a table of watts at evenly spaced utilizations, linear between
its points (``table_watts``), integrated over the piecewise-constant event
intervals the engine already produces, so per-DC energy falls out of the
same sweep that advances work.

Topology model: an inter-DC latency/bandwidth matrix (BRITE-style edge
parameters without the generator) replacing the paper's single scalar
inter-DC link; migration delay and federated placement cost become
pair-dependent, enabling locality-aware coordinator policies.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from jax import Array

from repro.core import policies
from repro.core.entities import Scenario, SimState
from repro.core.pytree import pytree_dataclass


@pytree_dataclass
class PowerModel:
    """Host power parameters, in one of two forms.

    Linear: ``watts_idle`` and ``watts_peak``, [D] each, idle to peak in
    proportion to utilization.  Table: ``table``, [D, H, P] watts of each
    host at P evenly spaced utilizations from 0% to 100%, linear between
    them (``table_watts``; the 11-point SPECpower tables of Beloglazov &
    Buyya's consolidation study, DESIGN.md §15).

    ``gate_idle`` models per-host power gating: a host with *no* VM holding
    resources on it draws zero instead of its idle power — the accounting
    that makes energy-consolidation migration (DESIGN.md §8) visible.  None
    (or all-False) keeps the classic always-on datacenter model.
    """
    watts_idle: Array | None = None   # drawn whenever a host is powered
    watts_peak: Array | None = None   # at 100% core-MIPS utilization
    gate_idle: Array | None = None    # [D] bool: unoccupied hosts draw 0
    table: Array | None = None        # [D, H, P] watts at 0%..100%

    @staticmethod
    def uniform(n_dc: int, idle: float = 93.0, peak: float = 135.0,
                gate_idle: bool = False):
        # defaults: SPECpower-ish numbers for a 2009-era 1U server
        return PowerModel(
            watts_idle=jnp.full((n_dc,), idle, jnp.float32),
            watts_peak=jnp.full((n_dc,), peak, jnp.float32),
            gate_idle=jnp.full((n_dc,), gate_idle, bool),
        )

    @staticmethod
    def from_tables(host_class, class_watts) -> "PowerModel":
        """The table form from each host's class (``[D, H]`` ints) and the
        classes' tables (``[classes, P]`` watts)."""
        watts = np.asarray(class_watts, np.float64)
        return PowerModel(table=jnp.asarray(
            watts[np.asarray(host_class, np.int64)], jnp.float32))


def table_watts(table: Array, util: Array) -> Array:
    """Watts at ``util`` (clipped to [0, 1]) from tables of P evenly spaced
    points (``[..., P]``, one per entry of ``util``), linear between them.
    The points are picked by one-hot sums, not gathers."""
    P = table.shape[-1]
    x = (P - 1) * jnp.clip(util, 0.0, 1.0)
    i = jnp.minimum(jnp.floor(x), P - 2).astype(jnp.int32)
    pts = jnp.arange(P, dtype=jnp.int32)
    wi = jnp.sum(jnp.where(pts == i[..., None], table, 0.0), axis=-1)
    wj = jnp.sum(jnp.where(pts == i[..., None] + 1, table, 0.0), axis=-1)
    return wi + (wj - wi) * (x - i.astype(jnp.float32))


@pytree_dataclass
class Topology:
    """Inter-DC link parameters, [D, D] each (diagonal = intra-DC).

    The single bandwidth surface for every inter-DC byte: migration images,
    evacuations, and cloudlet data staging all draw from these links through
    the ``SimState.link_busy`` / ``link_share`` ledger (DESIGN.md §13).
    """
    latency_s: Array
    bw_mbps: Array

    def fair_share(self, busy: Array) -> Array:
        """[D, D] Mbps each active transfer receives under fair sharing.

        ``busy`` is the per-link active-transfer count; an idle link grants
        its full capacity (``bw / max(busy, 1)``), so a lone transfer is
        bitwise-identical to the uncontended point-to-point divisor.
        """
        return self.bw_mbps / jnp.maximum(busy, 1).astype(jnp.float32)

    @staticmethod
    def uniform(n_dc: int, latency_s: float = 0.05, bw_mbps: float = 100.0):
        lat = jnp.full((n_dc, n_dc), latency_s, jnp.float32)
        lat = lat * (1 - jnp.eye(n_dc))
        bw = jnp.full((n_dc, n_dc), bw_mbps, jnp.float32)
        return Topology(latency_s=lat, bw_mbps=bw)

    @staticmethod
    def from_coordinates(coords_km: np.ndarray, bw_mbps: float = 100.0):
        """BRITE-flavoured: latency ~ great-circle distance / 0.6c."""
        d = np.linalg.norm(
            coords_km[:, None, :] - coords_km[None, :, :], axis=-1
        )
        lat = (d * 1e3 / (0.6 * 3e8)).astype(np.float32)
        n = coords_km.shape[0]
        return Topology(
            latency_s=jnp.asarray(lat),
            bw_mbps=jnp.full((n, n), bw_mbps, jnp.float32),
        )


def host_granted_mips(
    scn: Scenario, state: SimState, vm_mips: Array | None = None
) -> Array:
    """[D, H] MIPS currently granted to VMs on each host.

    ``vm_mips`` may be supplied by a caller that already ran the policy sweep
    (the engine's EnergyInstrument passes ``StepEvent.vm_mips``) so the grant
    is integrated over exactly the interval the sweep produced.
    """
    if vm_mips is None:
        vm_mips = policies.host_level_mips(scn, state)        # [V]
    D, H = scn.hosts.cores.shape
    seg = jnp.where(
        state.vm_placed & scn.vms.exists,
        state.vm_dc * H + state.vm_host,
        D * H,
    )
    return jnp.zeros((D * H + 1,), jnp.float32).at[
        jnp.clip(seg, 0, D * H)
    ].add(vm_mips)[:-1].reshape(D, H)


def host_utilization(
    scn: Scenario, state: SimState, vm_mips: Array | None = None
) -> Array:
    """[D, H] granted / capacity, clipped to [0, 1]; 0 for capacity-less hosts."""
    granted = host_granted_mips(scn, state, vm_mips)
    cap = scn.hosts.cores.astype(jnp.float32) * scn.hosts.mips
    return jnp.where(
        cap > 0, jnp.clip(granted / jnp.maximum(cap, 1e-9), 0, 1), 0.0
    )


def dc_utilization(
    scn: Scenario, state: SimState, vm_mips: Array | None = None
) -> Array:
    """[D] capacity-weighted datacenter utilization (the Sensor's CPU view)."""
    granted = jnp.where(
        scn.hosts.exists, host_granted_mips(scn, state, vm_mips), 0.0
    )
    cap = jnp.where(
        scn.hosts.exists,
        scn.hosts.cores.astype(jnp.float32) * scn.hosts.mips,
        0.0,
    )
    total_cap = jnp.sum(cap, axis=1)
    return jnp.where(
        total_cap > 0,
        jnp.clip(jnp.sum(granted, axis=1) / jnp.maximum(total_cap, 1e-9), 0, 1),
        0.0,
    )


def host_occupied(scn: Scenario, state: SimState) -> Array:
    """[D, H] bool — at least one VM currently holds resources on the host.

    A live-migrating VM occupies its *destination* slot from departure
    (provision.live_migrate reserves it), matching the free-capacity ledger.
    """
    D, H = scn.hosts.cores.shape
    occ = state.vm_placed & ~state.vm_released & scn.vms.exists
    seg = jnp.where(occ, state.vm_dc * H + state.vm_host, D * H)
    counts = jnp.zeros((D * H + 1,), jnp.int32).at[
        jnp.clip(seg, 0, D * H)
    ].add(occ.astype(jnp.int32))
    return counts[:-1].reshape(D, H) > 0


def power_draw(
    scn: Scenario, state: SimState, vm_mips: Array | None = None
) -> Array:
    """[D] instantaneous watts given the current allocation.

    Utilization per host = granted MIPS / capacity; idle power charged for
    every existing host — the paper's always-on datacenter framing — except
    hosts that are unoccupied under a ``gate_idle`` power model, which draw
    zero (the consolidation-migration payoff, DESIGN.md §8).
    """
    util = host_utilization(scn, state, vm_mips)
    pm: PowerModel = scn.power            # type: ignore[attr-defined]
    gated = getattr(pm, "gate_idle", None) is not None
    if pm.table is None:
        idle = jnp.broadcast_to(
            pm.watts_idle[:, None], scn.hosts.cores.shape
        )
        if gated:
            idle = jnp.where(
                pm.gate_idle[:, None] & ~host_occupied(scn, state), 0.0, idle
            )
        draw = idle + (pm.watts_peak - pm.watts_idle)[:, None] * util
    else:
        draw = table_watts(pm.table, util)
        if gated:
            draw = jnp.where(
                pm.gate_idle[:, None] & ~host_occupied(scn, state), 0.0, draw
            )
    # a failed host draws nothing — it is off, not idling (DESIGN.md §9)
    watts = jnp.where(scn.hosts.exists & state.host_up, draw, 0.0)
    return jnp.sum(watts, axis=1)


def migration_delay_matrix(
    scn: Scenario, image_mb: Array, policy=None
) -> Array:
    """[D, D] seconds to move a VM image between DC pairs under the topology.

    Includes ``Policy.migration_fixed_s`` (the VM re-creation latency), so the
    matrix agrees exactly with the uncontended delay the engine charges when a
    migration commits (provision.py) — analysis and placement consumers used
    to underestimate every move by the fixed term.  ``policy`` defaults to
    ``scn.policy``; pass one explicitly to price moves under a different knob
    setting without rebuilding the scenario.
    """
    topo: Topology = scn.topology         # type: ignore[attr-defined]
    pol = scn.policy if policy is None else policy
    return (
        pol.migration_fixed_s
        + topo.latency_s
        + image_mb / jnp.maximum(topo.bw_mbps, 1e-6)
    )
