"""Power-aware dynamic VM consolidation: CloudSim's ``power.planetlab`` suite
(Beloglazov & Buyya, CCPE 24(13), 2012), DESIGN.md §15.

A ``Consolidation`` attached as ``Scenario.dynamic_consolidation`` makes
each VM's CPU demand follow its own whole-percent utilisation series, one
sample per scheduling tick, held until the next; hosts grant it time-shared with
over-subscription (``vm_grant``).  At tick 0 ``initial_pass`` places every
VM by power-aware best-fit decreasing (PABFD) in index order; at every
later tick ``tick_pass`` (the step's ``phase_consolidate``):

1. appends each active host's demand to its history (the last ``HISTORY``
   ticks; a host with no VM forgets its history);
2. detects overloaded hosts by the row's detector: THR(s), IQR(s) or
   MAD(s), with THR(0.7) while a host has fewer than ``MIN_HISTORY``
   samples;
3. selects VMs off each overloaded host by minimum migration time (least
   RAM first, lowest index on ties) until the host is no longer overloaded;
4. places them by PABFD, largest demand first, never on an overloaded host;
5. drains underloaded hosts, least utilised first, all or nothing, onto
   hosts that are already on.

A move lasts ``RAM / (bw / 2)``; meanwhile the VM runs on its source at 90%
of its demand and its destination holds 10%.  Each pass ends by integrating
the interval to the next tick in one fold over its pieces between landings
(``_integrate``): energy, SLATAH and PDM, and the grant ``vm_grant`` reads.

Every decision compares int32 integers in host MIPS: demands are whole
MIPS, a detector threshold is ``800 * D + B > C`` with per-host integers
``B``, ``C`` (a statistic of the history times 8 is an integer), a power
increase is ``10 * cap * P`` in tenths of a watt, and hosts of unlike
capacity compare on one scale through ``Consolidation.key_mult``.  So the
float32 program decides exactly as a float64 reference does; only energy,
SLATAH and PDM accumulate in floating point.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import Array, lax

from repro.core import segments
from repro.core.entities import INF, Scenario, SimState
from repro.core.pytree import pytree_dataclass

# detectors (``ConsolidationPolicy.detector``)
THR, IQR, MAD = 0, 1, 2
HISTORY = 30          # CloudSim's PowerHostUtilizationHistory length
MIN_HISTORY = 12      # fewer samples: the THR(0.7) fallback
FALLBACK_S100 = 70
BW_DIVISOR = 16.0     # Mbit/s -> MB/s (8), half the link for the move (2)
_A = 800              # overloaded  <=>  _A * D + B > C  (see _thresholds)
_BIG = np.int32(2**31 - 1)
# the placement loops' scope, inside the step's ``phase_consolidate``
SCOPE_PLACE = "consolidate_place"
i32, f32 = jnp.int32, jnp.float32


@pytree_dataclass
class ConsolidationPolicy:
    """Overload detector and its parameter, traced so one compile serves
    every row of a policy sweep.  Minimum-migration-time selection and
    PABFD placement are the only selector and placement."""

    detector: Array   # scalar i32: THR | IQR | MAD
    param: Array      # scalar f32: THR's threshold, IQR's/MAD's safety
                      #   parameter (a multiple of 0.01)


@pytree_dataclass
class Consolidation:
    """What ``Scenario.dynamic_consolidation`` attaches (DESIGN.md §15)."""

    util: Array       # [V, K] i32 whole-percent demand of each VM per tick
    power: object     # energy.PowerModel, table form: 11 points per host
    key_mult: Array   # [D, H] i32 lcm of every host capacity / this one's
    policy: ConsolidationPolicy
    interval: Array   # scalar f32 seconds between ticks

    @property
    def n_ticks(self) -> int:
        return self.util.shape[-1]

    @staticmethod
    def build(util, host_class, class_watts, caps, policy: ConsolidationPolicy,
              interval) -> "Consolidation":
        """From each host's class (``[D, H]`` ints), the classes' 11-point
        tables in watts and each host's capacity in MIPS (cores x per-core
        MIPS, whole numbers).  The tables and capacities are configuration,
        checked here on the host: the watts must be whole tenths, so that
        ``10 * cap * P`` is an integer, and ``key_mult = lcm(caps) / cap``,
        which puts hosts of unlike capacity on one integer scale, must keep
        every power-increase key an int32."""
        from repro.core.energy import PowerModel

        host_class = np.asarray(host_class, np.int64)
        watts = np.asarray(class_watts, np.float64)
        tenths = np.rint(watts * 10).astype(np.int64)
        if watts.shape[-1] != 11 or np.abs(tenths - watts * 10).max() > 1e-6:
            raise ValueError("power tables need 11 points in tenths of a watt")
        caps = np.rint(np.asarray(caps, np.float64)).astype(np.int64)
        lcm = int(np.lcm.reduce(np.unique(caps[caps > 0])))
        mult = np.where(caps > 0, lcm // np.maximum(caps, 1), 0)
        # the largest key, 10 * cap * (P(1) - P(0)) * mult
        span = (tenths.max(-1) - tenths.min(-1))[host_class]
        if int((span * caps * mult).max()) >= 2**31:
            raise ValueError("host capacities too unlike for int32 power keys")
        return Consolidation(
            util=jnp.asarray(util, i32),
            power=PowerModel.from_tables(host_class, watts),
            key_mult=jnp.asarray(mult, i32), policy=policy,
            interval=jnp.asarray(interval, f32))


@pytree_dataclass
class PowerState:
    """``SimState.consol``: the pass's memory and the power accounting."""

    k: Array             # scalar i32 ticks processed
    d: Array             # [V] i32 demand (MIPS) since the last tick
    hist: Array          # [N, HISTORY] i32 host demand at past ticks, newest last
    hist_n: Array        # [N] i32 valid history samples
    mig_dst: Array       # [V] i32 destination host of a move in flight (-1)
    mig_end: Array       # [V] f32 when it lands
    energy_j: Array      # [N] f32
    t_full: Array        # [N] f32 seconds active at 100% demand
    t_active: Array      # [N] f32 seconds with a VM (resident or arriving)
    short: Array         # [V] f32 MIPS*s not granted while migrating
    req: Array           # [V] f32 MIPS*s requested
    n_overloaded: Array  # scalar i32 overloaded host detections
    n_place_tries: Array # scalar i32 PABFD placement decisions
    grant: Array         # [V] f32 MIPS granted, averaged to the next tick


@pytree_dataclass
class PowerResult:
    """``SimResult.power`` (Beloglazov & Buyya's metrics)."""

    energy_kwh: Array
    slatah: Array        # mean over ever-active hosts of time at 100% / active
    pdm: Array           # mean over VMs of migration shortfall / requested
    slav: Array          # slatah * pdm
    esv: Array           # energy_kwh * slav
    n_overloaded: Array
    n_place_tries: Array


def _tol(t):
    """Clock slack: float32 event times land within a few ulps of the
    stop they aimed at."""
    return 1e-6 * jnp.maximum(jnp.abs(t), 1.0)


def init_power_state(scn: Scenario) -> PowerState:
    N, V = scn.hosts.cores.size, scn.vms.n_vms
    return PowerState(
        k=jnp.asarray(0, i32), d=jnp.zeros((V,), i32),
        hist=jnp.zeros((N, HISTORY), i32), hist_n=jnp.zeros((N,), i32),
        mig_dst=jnp.full((V,), -1, i32), mig_end=jnp.full((V,), INF, f32),
        energy_j=jnp.zeros((N,), f32), t_full=jnp.zeros((N,), f32),
        t_active=jnp.zeros((N,), f32), short=jnp.zeros((V,), f32),
        req=jnp.zeros((V,), f32), n_overloaded=jnp.asarray(0, i32),
        n_place_tries=jnp.asarray(0, i32), grant=jnp.zeros((V,), f32),
    )


# ---------------------------------------------------------------------------
# hosts, VMs and the integer power model
# ---------------------------------------------------------------------------

def _rint(x) -> Array:
    return jnp.rint(x).astype(i32)


def _hosts(scn: Scenario, st: SimState) -> dict:
    """Flat ``[N]`` host columns in whole MIPS, MB and Mbit/s."""
    h, cs = scn.hosts, scn.dynamic_consolidation
    N = h.cores.size
    return {
        "N": N, "H": h.n_hosts,
        "cap": _rint(h.cores.astype(f32) * h.mips).reshape(N),
        "percore": _rint(h.mips).reshape(N),
        "ram": _rint(h.ram_mb).reshape(N),
        "bw": _rint(h.bw_mbps).reshape(N),
        "ok": (h.exists & st.host_up).reshape(N),
        "tenths": _rint(10.0 * cs.power.table).reshape(N, 11),
        "mult": cs.key_mult.reshape(N),
    }


def _power_scaled(tenths: Array, cap: Array, D: Array) -> Array:
    """``10 * cap * P(min(D, cap) / cap)`` in tenths of a watt: an exact
    integer, linear between the table's 10% points."""
    Dc = jnp.clip(D, 0, cap)
    i = jnp.minimum(10 * Dc // jnp.maximum(cap, 1), 9)
    pts = jnp.arange(11, dtype=i32)
    wi = jnp.sum(jnp.where(pts == i[..., None], tenths, 0), axis=-1)
    wj = jnp.sum(jnp.where(pts == i[..., None] + 1, tenths, 0), axis=-1)
    return wi * cap + (wj - wi) * (10 * Dc - i * cap)


def _demand(scn: Scenario, k) -> Array:
    cs, vms = scn.dynamic_consolidation, scn.vms
    u = lax.dynamic_index_in_dim(
        cs.util, jnp.clip(k, 0, cs.n_ticks - 1), axis=1, keepdims=False)
    return jnp.where(vms.exists, u * _rint(vms.mips) // 100, 0)


def _resident(scn: Scenario, st: SimState) -> tuple[Array, Array]:
    """([V] resident mask, [V] flat host index or -1)."""
    res = (scn.vms.exists & st.vm_placed & ~st.vm_released & ~st.vm_failed)
    host = jnp.where(res, st.vm_dc * scn.hosts.n_hosts + st.vm_host, -1)
    return res, host


def _per_host(x: Array, host: Array, N: int) -> Array:
    return segments.segment_sum(x, jnp.where(host >= 0, host, N), N)


def _pieces(scn: Scenario, st: SimState, t0, t1, fold, acc):
    """Fold ``fold(acc, piece)`` over the pieces of ``[t0, t1)`` between
    the landings of the moves in flight.  Landings are not clock stops: a
    piece holds each host's demand constant, ten times it in ``D10`` with a
    moving VM's source counting 90% of the VM and its destination 10%
    until the move lands and all of it after.  ``piece`` is ``(D10 [N],
    on [N]: the host holds a VM, at [V]: each VM's host, moving [V],
    length)``."""
    ps = st.consol
    N = scn.hosts.cores.size
    res, host = _resident(scn, st)
    mig = res & (ps.mig_dst >= 0)
    land = jnp.where(mig, ps.mig_end, INF)
    ones = res.astype(i32)

    def body(c):
        a, acc = c
        moving = mig & (land > a + _tol(a))
        b = jnp.minimum(t1, jnp.min(jnp.where(moving, land, INF)))
        at = jnp.where(mig & ~moving, ps.mig_dst, host)
        dst = jnp.where(moving, ps.mig_dst, -1)
        # one scatter for both host totals: demand x 10, and VMs held
        idx = jnp.concatenate([jnp.where(at >= 0, at, N),
                               jnp.where(dst >= 0, dst, N)])
        val = jnp.stack([
            jnp.concatenate([jnp.where(moving, 9, 10) * ps.d, ps.d]),
            jnp.concatenate([ones, moving.astype(i32)])], axis=1)
        tot = jnp.zeros((N + 1, 2), i32).at[idx].add(val)[:N]
        return b, fold(acc, (tot[:, 0], tot[:, 1] > 0, at, moving, b - a))

    return lax.while_loop(lambda c: c[0] < t1, body, (t0, acc))[1]


def _grant(scn: Scenario, piece, d: Array) -> Array:
    """[V] MIPS each resident VM is granted in a piece: its demand (90%
    while migrating out) scaled by ``min(1, cap / demand)`` of its host."""
    D10, _, at, moving, _ = piece
    N = D10.shape[0]
    cap10 = 10.0 * (scn.hosts.cores.astype(f32) * scn.hosts.mips).reshape(N)
    scale = jnp.minimum(1.0, cap10 / jnp.maximum(D10, 1).astype(f32))
    eff = jnp.where(moving, 0.9, 1.0) * d
    return jnp.where(at >= 0, eff * scale[jnp.clip(at, 0, N - 1)], 0.0)


def vm_grant(scn: Scenario, st: SimState) -> Array:
    """[V] f32 MIPS each VM is granted until the next tick, averaged over
    the pieces between landings (``_integrate``), so the work a VM's
    cloudlet does by each tick is exact."""
    return st.consol.grant


# ---------------------------------------------------------------------------
# the detectors: overloaded  <=>  _A * D + B > C
# ---------------------------------------------------------------------------

def _pct_scaled(xs: Array, n: Array, numer: int, den: int) -> Array:
    """``den`` times the percentile at ``numer / den`` of each row's first
    ``n`` sorted values, Commons Math's default estimation: position
    ``p (n + 1)``, clamped to the ends, linear between neighbours."""
    pos = numer * (n + 1)
    fl, fr = pos // den, pos % den
    last = xs.shape[-1] - 1

    def at(j):
        return jnp.take_along_axis(xs, jnp.clip(j, 0, last)[:, None], 1)[:, 0]

    lo, hi = at(fl - 1), at(fl)
    mid = den * lo + fr * (hi - lo)
    return jnp.where(fl < 1, den * xs[:, 0],
                     jnp.where(fl >= n, den * at(n - 1), mid))


def _thresholds(policy: ConsolidationPolicy, cap: Array, hist: Array,
                hist_n: Array) -> tuple[Array, Array]:
    """Per-host ``(B, C)``.  THR(s): ``100 D > 100s cap``.  IQR(s), MAD(s):
    ``u > 1 - s stat / cap``, i.e. ``800 D + 100s (8 stat) > 800 cap``; 8
    times a quartile spread or a median deviation of whole-MIPS values is
    an integer."""
    s100 = _rint(policy.param * 100.0)
    H = hist.shape[-1]
    valid = jnp.arange(H)[None, :] >= H - hist_n[:, None]
    xs = jnp.sort(jnp.where(valid, hist, _BIG), axis=1)   # valid first
    iqr8 = 2 * (_pct_scaled(xs, hist_n, 3, 4) - _pct_scaled(xs, hist_n, 1, 4))
    med2 = _pct_scaled(xs, hist_n, 1, 2)
    first = jnp.arange(H)[None, :] < hist_n[:, None]
    dev2 = jnp.sort(jnp.where(first, jnp.abs(2 * xs - med2[:, None]), _BIG),
                    axis=1)
    mad8 = 2 * _pct_scaled(dev2, hist_n, 1, 2)
    thr = policy.detector == THR
    plain = thr | (hist_n < MIN_HISTORY)
    s_thr = jnp.where(thr, s100, FALLBACK_S100)
    stat8 = jnp.where(policy.detector == IQR, iqr8, mad8)
    B = jnp.where(plain, 0, s100 * stat8)
    C = jnp.where(plain, 8 * s_thr * cap, _A * cap)
    return B, C


def _over(B, C, D) -> Array:
    return _A * D + B > C


# ---------------------------------------------------------------------------
# planning: a VM -> host assignment and the host totals it implies
# ---------------------------------------------------------------------------

def _plan(hc: dict, vc: dict, host: Array) -> tuple:
    """``(host [V], D, ram_free, bw_free, count, power [N])`` of an
    assignment, ``power`` being ``_power_scaled`` of ``D``."""
    N = hc["N"]
    D = _per_host(vc["d"], host, N)
    return (host, D,
            hc["ram"] - _per_host(vc["ram"], host, N),
            hc["bw"] - _per_host(vc["bw"], host, N),
            _per_host(jnp.ones_like(host), host, N),
            _power_scaled(hc["tenths"], hc["cap"], D))


def _vm(vc: dict, plan: tuple, v) -> tuple:
    """VM ``v``'s demand, RAM and bandwidth requests and planned host, as
    one-hot sums over the VMs: sibling reductions fuse into one kernel,
    where a gather per field costs a kernel each."""
    at = jnp.arange(plan[0].shape[0], dtype=i32) == v
    return tuple(jnp.sum(jnp.where(at, x, 0))
                 for x in (vc["d"], vc["ram"], vc["bw"], plan[0]))


def _move(plan: tuple, v, vm: tuple, h, Fn: Array) -> tuple:
    """Plan VM ``v`` (``vm``: ``_vm``'s row) onto host ``h``; ``Fn`` is
    every host's power with the VM added (``_best_host``'s).  Elementwise
    updates, no scatter.  A host's power is kept only where the VM lands:
    the host it leaves can take no VM for the rest of the tick (an
    overloaded host while the taken VMs are placed, the drained candidate
    during the underload drain), so its power is never read again."""
    host, D, ram, bw, cnt, F = plan
    dv, rv, bv, src = vm
    hid = jnp.arange(D.shape[0], dtype=i32)
    to = hid == h
    step = to.astype(i32) - (hid == src).astype(i32)
    vid = jnp.arange(host.shape[0], dtype=i32)
    return (jnp.where(vid == v, h, host), D + dv * step, ram - rv * step,
            bw - bv * step, cnt + step, jnp.where(to, Fn, F))


def _select(pred, a, b):
    return jax.tree.map(lambda x, y: jnp.where(pred, x, y), a, b)


def _best_host(hc: dict, vc: dict, B, C, plan: tuple, v, allowed: Array):
    """PABFD for VM ``v``: the allowed host with the least power increase
    (lowest index on ties) that has the free MIPS, the per-core MIPS, the
    RAM and the bandwidth, and is not overloaded with the VM.  Returns
    ``(host, found, every host's power with the VM, the VM's row)``."""
    _, D, ram, bw, _, F = plan
    vm = _vm(vc, plan, v)
    dv, rv, bv, _ = vm
    Dn = D + dv
    ok = (allowed & (hc["cap"] - D >= dv) & (hc["percore"] >= dv)
          & (ram >= rv) & (bw >= bv) & ~_over(B, C, Dn))
    Fn = _power_scaled(hc["tenths"], hc["cap"], Dn)
    # every key is below _BIG (Consolidation.build checks the bound)
    key = jnp.where(ok, (Fn - F) * hc["mult"], _BIG)
    return jnp.argmin(key).astype(i32), jnp.min(key) < _BIG, Fn, vm


def _place_in_turn(hc, vc, B, C, plan, pending, allowed, tries):
    """Place the ``pending`` VMs one by one in ``vc["rank"]`` order; a VM
    no host takes stays where it is."""
    V = vc["d"].shape[0]

    def body(c):
        plan, pending, left, tries = c
        v = jnp.argmin(jnp.where(pending, vc["rank"], _BIG))
        h, ok, Fn, vm = _best_host(hc, vc, B, C, plan, v, allowed)
        plan = _select(ok, _move(plan, v, vm, h, Fn), plan)
        return plan, pending & (jnp.arange(V) != v), left - 1, tries + 1

    left = jnp.sum(pending.astype(i32))
    plan, _, _, tries = lax.while_loop(
        lambda c: c[2] > 0, body, (plan, pending, left, tries))
    return plan, tries


def _drain_underloaded(hc, vc, B, C, plan, over, tries):
    """The underload drain: take the active, unexcluded host of least
    utilisation, exclude it from now on, and plan all its VMs onto hosts that are on,
    unexcluded and not overloaded; keep the plan only if every VM fits,
    and then exclude its destinations as candidates.  One VM per loop
    iteration; the next candidate is picked as the current one ends."""
    N, V = hc["N"], vc["d"].shape[0]

    def pick(plan, excl_c, excl_d):
        """The next candidate: exclusions, its VMs, and how many."""
        key = jnp.where((plan[4] > 0) & ~excl_c, plan[1] * hc["mult"], _BIG)
        c = jnp.argmin(key).astype(i32)
        has = jnp.min(key) < _BIG
        here = has & (jnp.arange(N) == c)
        return (excl_c | here, excl_d | here, has & (plan[0] == c),
                jnp.sum(jnp.where(here, plan[4], 0)))

    none = jnp.zeros((N,), bool)

    def body(c):
        plan, snap, excl_c, excl_d, pending, left, dsts, tries = c
        v = jnp.argmin(jnp.where(pending, vc["rank"], _BIG))
        allowed = hc["ok"] & (plan[4] > 0) & ~excl_d
        h, ok, Fn, vm = _best_host(hc, vc, B, C, plan, v, allowed)
        plan = _select(ok, _move(plan, v, vm, h, Fn), snap)   # a miss: roll back
        pending = pending & (jnp.arange(V) != v)
        left = jnp.where(ok, left - 1, 0)
        dsts = dsts | (ok & (jnp.arange(N) == h))
        done = left == 0
        excl_c = jnp.where(done & ok, excl_c | dsts, excl_c)
        nc, nd, npend, nleft = pick(plan, excl_c, excl_d)
        return (plan, _select(done, plan, snap),
                jnp.where(done, nc, excl_c), jnp.where(done, nd, excl_d),
                jnp.where(done, npend, pending), jnp.where(done, nleft, left),
                jnp.where(done, none, dsts), tries + 1)

    excl_c, excl_d, pending, left = pick(plan, over | ~hc["ok"],
                                         over | ~hc["ok"])
    out = lax.while_loop(
        lambda c: c[5] > 0, body,
        (plan, plan, excl_c, excl_d, pending, left, none, tries))
    return out[0], out[7]


def _mmt(vc: dict, B, C, plan: tuple, over: Array) -> Array:
    """[V] VMs taken off overloaded hosts: in (host, RAM, index) order,
    each VM whose host is still overloaded without the VMs before it."""
    host, D = plan[0], plan[1]
    N = D.shape[0]
    V = host.shape[0]
    idx = jnp.arange(V, dtype=i32)
    hs, _, vs = lax.sort((jnp.where(host >= 0, host, N), vc["image"], idx),
                         num_keys=3)
    ds = vc["d"][vs]
    before = jnp.cumsum(ds) - ds
    first = jnp.concatenate([jnp.ones((1,), bool), hs[1:] != hs[:-1]])
    start = lax.cummax(jnp.where(first, idx, 0))
    before = before - before[start]
    hh = jnp.minimum(hs, N - 1)
    take = (hs < N) & over[hh] & _over(B[hh], C[hh], D[hh] - before)
    return jnp.zeros((V,), bool).at[vs].set(take)


# ---------------------------------------------------------------------------
# the phase: tick predicate, the two passes, landings, accounting
# ---------------------------------------------------------------------------

def tick_due(scn: Scenario, st: SimState) -> Array:
    cs, k = scn.dynamic_consolidation, st.consol.k
    return (k < cs.n_ticks) & (
        st.t >= k.astype(f32) * cs.interval - _tol(st.t))


def _vms(scn: Scenario, d: Array, rank: Array, created: bool) -> dict:
    """The VMs' requests: demand, and RAM and bandwidth in full while
    being created, none once running (CloudSim's planetlab examples model
    both with a null utilisation model, DESIGN.md §15)."""
    vms = scn.vms
    keep = 1 if created else 0
    ram = _rint(vms.ram_mb)
    return {"d": d, "ram": keep * ram, "bw": keep * _rint(vms.bw_mbps),
            "rank": rank, "image": ram}


def initial_pass(scn: Scenario, st: SimState) -> SimState:
    """Tick 0: every VM by PABFD in index order, the detector's threshold
    on empty histories; a VM no host takes fails creation."""
    ps = st.consol
    hc = _hosts(scn, st)
    d = _demand(scn, 0)
    V = d.shape[0]
    vc = _vms(scn, d, jnp.arange(V, dtype=i32), created=True)
    B, C = _thresholds(scn.dynamic_consolidation.policy, hc["cap"], ps.hist,
                       jnp.zeros_like(ps.hist_n))
    plan0 = _plan(hc, vc, jnp.full((V,), -1, i32))

    def body(v, c):
        plan, tries = c
        h, ok, Fn, vm = _best_host(hc, vc, B, C, plan, v, hc["ok"])
        go = scn.vms.exists[v]
        plan = _select(go & ok, _move(plan, v, vm, h, Fn), plan)
        return plan, tries + go.astype(i32)

    with jax.named_scope(SCOPE_PLACE):
        plan, tries = lax.fori_loop(0, V, body, (plan0, ps.n_place_tries))
    host = plan[0]
    placed = host >= 0
    H = hc["H"]
    st = st.replace(
        vm_host=jnp.where(placed, host % H, st.vm_host),
        vm_dc=jnp.where(placed, host // H, st.vm_dc),
        vm_placed=st.vm_placed | placed,
        vm_failed=st.vm_failed | (scn.vms.exists & ~placed),
        vm_avail_t=jnp.where(placed, st.t, st.vm_avail_t),
        consol=ps.replace(k=ps.k + 1, d=d, n_place_tries=tries),
    )
    return _integrate(scn, st)


def tick_pass(scn: Scenario, st: SimState) -> SimState:
    """A scheduling tick after the first: history, detection, MMT
    selection, PABFD placement, the underload drain, and the moves."""
    ps, cs = st.consol, scn.dynamic_consolidation
    hc = _hosts(scn, st)
    d = _demand(scn, ps.k)
    V = d.shape[0]
    idx = jnp.arange(V, dtype=i32)
    _, order = lax.sort((-d, idx), num_keys=2)
    vc = _vms(scn, d, jnp.zeros((V,), i32).at[order].set(idx),
              created=False)
    res, host = _resident(scn, st)
    plan = _plan(hc, vc, host)
    active = plan[4] > 0
    hist = jnp.where(active[:, None], jnp.concatenate(
        [ps.hist[:, 1:], plan[1][:, None]], axis=1), ps.hist)
    hist_n = jnp.where(active, jnp.minimum(ps.hist_n + 1, HISTORY), 0)
    B, C = _thresholds(cs.policy, hc["cap"], hist, hist_n)
    over = active & _over(B, C, plan[1])
    taken = _mmt(vc, B, C, plan, over)
    with jax.named_scope(SCOPE_PLACE):
        plan, tries = _place_in_turn(hc, vc, B, C, plan, taken,
                                     hc["ok"] & ~over, ps.n_place_tries)
        plan, tries = _drain_underloaded(hc, vc, B, C, plan, over, tries)
    dst = plan[0]
    moved = res & (dst != host)
    bw = scn.hosts.bw_mbps.reshape(hc["N"])[jnp.clip(dst, 0, hc["N"] - 1)]
    delay = scn.vms.ram_mb / (bw / BW_DIVISOR)
    st = st.replace(
        vm_migrations=st.vm_migrations + moved.astype(i32),
        consol=ps.replace(
            k=ps.k + 1, d=d, hist=hist, hist_n=hist_n,
            mig_dst=jnp.where(moved, dst, -1),
            mig_end=jnp.where(moved, st.t + delay, INF),
            n_overloaded=ps.n_overloaded + jnp.sum(over.astype(i32)),
            n_place_tries=tries),
    )
    return _integrate(scn, st)


def gated(fn, pred, scn: Scenario, st: SimState) -> SimState:
    """``fn(scn, st)`` where ``pred``, else ``st`` (a row of a batch)."""
    return _select(pred, fn(scn, st), st)


def settle_landings(scn: Scenario, st: SimState) -> SimState:
    """Moves whose transfer has ended: the VM now lives on its
    destination."""
    ps = st.consol
    H = scn.hosts.n_hosts
    landed = (ps.mig_dst >= 0) & (ps.mig_end <= st.t + _tol(st.t))
    return st.replace(
        vm_host=jnp.where(landed, ps.mig_dst % H, st.vm_host),
        vm_dc=jnp.where(landed, ps.mig_dst // H, st.vm_dc),
        consol=ps.replace(mig_dst=jnp.where(landed, -1, ps.mig_dst),
                          mig_end=jnp.where(landed, INF, ps.mig_end)))


def next_tick(scn: Scenario, st: SimState) -> Array:
    """The next scheduling tick as an absolute clock stop; moves land
    between ticks without one (``_pieces``)."""
    cs, ps = scn.dynamic_consolidation, st.consol
    return jnp.where(ps.k < cs.n_ticks, ps.k.astype(f32) * cs.interval, INF)


def _integrate(scn: Scenario, st: SimState) -> SimState:
    """The interval from this tick to the next, integrated when the pass
    has decided it, in one fold over its pieces: each host's power from
    its table at ``min(u, 1)`` (0 W without a VM), its time active and at
    100% demand, each VM's requested MIPS*s and, while migrating, its
    shortfall of demand over grant; and each VM's grant averaged over the
    interval (``vm_grant``)."""
    from repro.core.energy import table_watts

    ps = st.consol
    N = scn.hosts.cores.size
    cap10 = 10.0 * (scn.hosts.cores.astype(f32) * scn.hosts.mips).reshape(N)
    table = scn.dynamic_consolidation.power.table.reshape(N, -1)
    d = ps.d.astype(f32)
    t1 = jnp.minimum(next_tick(scn, st), scn.policy.horizon)

    def fold(acc, piece):
        energy, full, active, req, short, work = acc
        D10, on, at, moving, T = piece
        D10f = D10.astype(f32)
        watts = table_watts(table, D10f / jnp.maximum(cap10, 1.0))
        g = _grant(scn, piece, d)
        return (energy + jnp.where(on, watts * T, 0.0),
                full + jnp.where(on & (D10f >= cap10), T, 0.0),
                active + jnp.where(on, T, 0.0),
                req + jnp.where(at >= 0, d * T, 0.0),
                short + jnp.where(moving, (d - g) * T, 0.0),
                work + g * T)

    energy, full, active, req, short, work = _pieces(
        scn, st, st.t, t1, fold,
        (ps.energy_j, ps.t_full, ps.t_active, ps.req, ps.short,
         jnp.zeros_like(d)))
    return st.replace(consol=ps.replace(
        energy_j=energy, t_full=full, t_active=active, req=req, short=short,
        grant=work / jnp.maximum(t1 - st.t, 1e-30)))


def finalize(scn: Scenario, st: SimState) -> PowerResult:
    ps = st.consol
    energy = jnp.sum(ps.energy_j) / 3.6e6
    ever = ps.t_active > 0
    slatah = jnp.sum(jnp.where(
        ever, ps.t_full / jnp.maximum(ps.t_active, 1e-30), 0.0)) / jnp.maximum(
        jnp.sum(ever.astype(f32)), 1.0)
    ex = scn.vms.exists
    per_vm = jnp.where(ex & (ps.req > 0),
                       ps.short / jnp.maximum(ps.req, 1e-30), 0.0)
    pdm = jnp.sum(per_vm) / jnp.maximum(jnp.sum(ex.astype(f32)), 1.0)
    slav = slatah * pdm
    return PowerResult(energy_kwh=energy, slatah=slatah, pdm=pdm, slav=slav,
                       esv=energy * slav, n_overloaded=ps.n_overloaded,
                       n_place_tries=ps.n_place_tries)
