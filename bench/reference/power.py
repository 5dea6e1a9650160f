"""Plain reference of power-aware VM consolidation (Beloglazov & Buyya, CCPE
24(13), 2012, as CloudSim 3.0's ``power.planetlab`` examples run it).

A numpy loop over scheduling ticks, one row at a time, written from the
semantics the configuration states (``bench/configs/planetlab_power.json``)
and nothing of the code under test.  Within a tick, hosts are arrays and
decisions are taken one after another:

1. tick 0: every VM in index order by power-aware best-fit decreasing
   (PABFD, step 4) with no host excluded;
2. later ticks: each host with a VM appends its demand to its history (the
   last 30; a host without a VM forgets it), and is overloaded when
   THR(s): ``u > s``, or IQR(s) / MAD(s): ``u > 1 - s * stat(history) /
   cap``, with THR(0.7) below 12 samples; percentiles are Commons Math's
   default (position ``p (n + 1)``, linear between neighbours);
3. minimum migration time: each overloaded host, in host order, gives up
   VMs least RAM first (lowest index on ties) until it is not overloaded;
4. PABFD: the taken VMs by demand, largest first (lowest index on ties),
   each to the allowed host with the least power increase (lowest index on
   ties) that has the free MIPS, the per-core MIPS, the RAM and the
   bandwidth the VM requests, and is not overloaded with the VM;
   overloaded hosts are not allowed.  A VM requests its RAM and bandwidth
   while it is created (tick 0) and none once it runs, as CloudSim's
   planetlab examples model both (a null utilisation model);
5. underload: hosts off or overloaded are left out; the active host of
   least utilisation is taken and left out from then on, as a candidate
   and as a destination; its VMs are planned by step 4 onto hosts that are
   on; if all fit the plan is kept and its destinations are no longer
   candidates, else none of it is kept;
6. a move lasts ``RAM / (bw / 16)`` seconds (MB over half the Mbit/s link)
   and lands before the next tick; until it lands the VM runs on its
   source at 90% of its demand and its destination holds 10%.  Each tick
   is one event: landings split the interval for the integrals below but
   stop no clock.

Power is linear between an 11-point table's 10% points at ``min(u, 1)``;
a host with no VM draws nothing.  Energy, time at 100% (SLATAH), the
shortfall of migrating VMs (PDM) and the requested MIPS*s integrate over
the intervals between ticks and landings.

Every comparison that decides is made on whole numbers held exactly in
float64: demands in MIPS, thresholds scaled by 100, power increases as
``10 * cap * P`` scaled by ``lcm(caps) / cap``.  ``dtype`` is the precision
of demands and of the accounting (float64 here; the control of the check
runs the same loop in bfloat16).
"""
from __future__ import annotations

import math

import numpy as np

THR, IQR, MAD = 0, 1, 2
HISTORY, MIN_HISTORY = 30, 12


def rows_from_config(config: dict, params: dict) -> dict:
    """The deployment of ``config`` and the rows of ``params`` as numpy
    arrays (``detector``, ``param`` per row; ``util`` ``[V, K]`` shared)."""
    dep = config["deployment"]
    h, v = dep["hosts"], dep["vms"]
    classes = h["classes"]
    N = h["count"]
    cls = np.arange(N) % len(classes)
    cap = np.array([classes[c]["cores"] * classes[c]["mips"] for c in cls],
                   np.float64)
    types = v["types"]
    V = v["count"]
    ty = np.arange(V) * len(types) // V
    return {
        "cap": cap,
        "percore": np.array([classes[c]["mips"] for c in cls], np.float64),
        "watts": np.array([classes[c]["watts"] for c in cls], np.float64),
        "host_ram": np.full(N, float(h["ram_mb"])),
        "host_bw": np.full(N, float(h["bw_mbps"])),
        "vm_mips": np.array([types[t]["mips"] for t in ty], np.float64),
        "vm_ram": np.array([types[t]["ram_mb"] for t in ty], np.float64),
        "vm_bw": np.full(V, float(v["bw_mbps"])),
        "util": np.asarray(params["util"], np.float64),
        "interval": float(dep["day"]["interval_s"]),
        "detector": np.asarray(params["detector"], np.int64),
        "param": np.asarray(params["param"], np.float64),
    }


def _percentile(xs: np.ndarray, p: float) -> float:
    """Commons Math's default percentile of sorted ``xs``."""
    n = len(xs)
    pos = p * (n + 1) / 100.0
    if pos < 1:
        return float(xs[0])
    if pos >= n:
        return float(xs[-1])
    fl = int(math.floor(pos))
    lo, hi = xs[fl - 1], xs[fl]
    return float(lo + (pos - fl) * (hi - lo))


def _threshold_fn(detector: int, s100: int, cap, hist, hist_n):
    """``over(D, h)``: is host ``h`` overloaded at demand ``D``.  THR(s):
    ``D / cap > s``; IQR(s), MAD(s): ``D / cap > 1 - s * stat / cap``;
    both as ``100 D > T`` with a whole-number ``T`` per host."""
    T = (s100 if detector == THR else 70) * cap
    if detector != THR:
        for h in np.flatnonzero(hist_n >= MIN_HISTORY):
            xs = np.sort(hist[h][-hist_n[h]:])
            if detector == IQR:
                stat = _percentile(xs, 75) - _percentile(xs, 25)
            else:
                med = _percentile(xs, 50)
                stat = _percentile(np.sort(np.abs(xs - med)), 50)
            T[h] = 100 * cap[h] - s100 * stat

    def over(D, h=slice(None)):
        return 100 * D > T[h]

    over.T = T
    return over


class _Row:
    """One row's hosts and VMs, and the power model, in whole numbers."""

    def __init__(self, R: dict, dtype):
        self.R, self.dtype = R, dtype
        self.cap = R["cap"]
        self.tenths = np.rint(R["watts"] * 10)
        caps = np.rint(self.cap).astype(np.int64)
        self.mult = (np.lcm.reduce(np.unique(caps)) // caps).astype(np.float64)
        self.cap_l, self.tenths_l = self.cap.tolist(), self.tenths.tolist()
        self.tenths_flat = self.tenths.reshape(-1)

    def power10(self, D, at=None):
        """``10 * cap * P(min(D, cap) / cap)`` in tenths of a watt, for every
        host or the hosts ``at``."""
        at = np.arange(len(self.cap)) if at is None else at
        cap = self.cap[at]
        Dc = np.minimum(np.maximum(D, 0), cap)
        i = np.minimum(10 * Dc // cap, 9).astype(np.int64)
        j = at * 11 + i
        wi, wj = self.tenths_flat[j], self.tenths_flat[j + 1]
        return wi * cap + (wj - wi) * (10 * Dc - i * cap)

    def power10_at(self, h: int, D) -> float:
        """``power10`` of one host, in plain Python arithmetic."""
        cap = self.cap_l[h]
        Dc = min(max(D, 0), cap)
        i = min(math.floor(10 * Dc / cap), 9)
        wi, wj = self.tenths_l[h][i], self.tenths_l[h][i + 1]
        return wi * cap + (wj - wi) * (10 * Dc - i * cap)

    def best(self, plan, v, allowed, over):
        """PABFD's host for VM ``v``, or -1."""
        dv = plan["d"][v]
        D = plan["D"]
        ok = (allowed & (D <= self.cap - dv) & (100 * (D + dv) <= over.T)
              & (self.R["percore"] >= dv))
        if plan["created"]:
            ok &= ((plan["ram"] >= plan["req_ram"][v])
                   & (plan["bw"] >= plan["req_bw"][v]))
        at = np.flatnonzero(ok)
        if not len(at):
            return -1
        key = (self.power10(D[at] + dv, at) - plan["P"][at]) * self.mult[at]
        return int(at[np.argmin(key)])

    def move(self, plan, v, h):
        d = plan["d"]
        src = plan["host"][v]
        for s, sign in ((src, -1), (h, 1)):
            if s >= 0:
                plan["D"][s] += sign * d[v]
                plan["ram"][s] -= sign * plan["req_ram"][v]
                plan["bw"][s] -= sign * plan["req_bw"][v]
                plan["cnt"][s] += sign
                plan["P"][s] = self.power10_at(s, plan["D"][s])
        plan["host"][v] = h


def _plan(row: _Row, host, d, created: bool) -> dict:
    """Planning state; VMs request their RAM and bandwidth only while
    being created (tick 0), none once running."""
    R = row.R
    N = len(row.cap)
    res = host >= 0
    hs = host[res]
    D = np.zeros(N, row.dtype)
    np.add.at(D, hs, d[res])
    ram, bw, cnt = np.zeros(N), np.zeros(N), np.zeros(N, np.int64)
    req_ram = R["vm_ram"] * created
    req_bw = R["vm_bw"] * created
    np.add.at(ram, hs, req_ram[res])
    np.add.at(bw, hs, req_bw[res])
    np.add.at(cnt, hs, 1)
    return {"host": host.copy(), "D": D, "ram": R["host_ram"] - ram,
            "bw": R["host_bw"] - bw, "cnt": cnt, "d": d, "P": row.power10(D), "created": created,
            "req_ram": req_ram, "req_bw": req_bw}


def _copy(plan):
    return {k: (v.copy() if k in ("host", "D", "ram", "bw", "cnt", "P")
                else v)
            for k, v in plan.items()}


def simulate_row(R: dict, i: int, dtype=np.float64) -> dict:
    """One row of ``rows_from_config``: its moves, events and metrics."""
    row = _Row(R, dtype)
    cap = row.cap
    N, (V, K) = len(cap), R["util"].shape
    det, s100 = int(R["detector"][i]), int(round(R["param"][i] * 100))
    interval = R["interval"]
    host = np.full(V, -1, np.int64)
    hist, hist_n = np.zeros((N, HISTORY)), np.zeros(N, np.int64)
    energy, t_full, t_active = (np.zeros(N, dtype) for _ in range(3))
    req, short = np.zeros(V, dtype), np.zeros(V, dtype)
    moves, n_events, tries, n_over = [], 0, 0, 0
    allow_all = np.ones(N, bool)

    for k in range(K):
        d = (R["util"][:, k] * R["vm_mips"] / 100).astype(dtype)
        plan = _plan(row, host, d, created=k == 0)
        src = host.copy()
        if k == 0:
            over = _threshold_fn(det, s100, cap, hist, np.zeros(N, np.int64))
            for v in range(V):
                tries += 1
                h = row.best(plan, v, allow_all, over)
                if h >= 0:
                    row.move(plan, v, h)
        else:
            active = plan["cnt"] > 0
            D = plan["D"]
            hist = np.where(active[:, None],
                            np.concatenate([hist[:, 1:], D[:, None]], 1), hist)
            hist_n = np.where(active, np.minimum(hist_n + 1, HISTORY), 0)
            over = _threshold_fn(det, s100, cap, hist, hist_n)
            hot = active & over(D)
            n_over += int(hot.sum())
            taken = []
            for h in np.flatnonzero(hot):
                on = np.flatnonzero(plan["host"] == h)
                Dh = D[h]
                for v in sorted(on, key=lambda v: (R["vm_ram"][v], v)):
                    if not over(Dh, h):
                        break
                    taken.append(v)
                    Dh = Dh - d[v]
            for v in sorted(taken, key=lambda v: (-d[v], v)):
                tries += 1
                h = row.best(plan, v, ~hot, over)
                if h >= 0:
                    row.move(plan, v, h)
            excl_c, excl_d = hot.copy(), hot.copy()
            while True:
                cand = np.flatnonzero((plan["cnt"] > 0) & ~excl_c)
                if not len(cand):
                    break
                c = int(cand[np.argmin((plan["D"] * row.mult)[cand])])
                excl_c[c] = excl_d[c] = True
                trial, dsts = _copy(plan), []
                for v in sorted(np.flatnonzero(plan["host"] == c),
                                key=lambda v: (-d[v], v)):
                    tries += 1
                    h = row.best(trial, v, (trial["cnt"] > 0) & ~excl_d, over)
                    if h < 0:
                        dsts = None
                        break
                    row.move(trial, v, h)
                    dsts.append(h)
                if dsts is not None:
                    plan = trial
                    excl_c[dsts] = True
        host = plan["host"]
        moved = np.flatnonzero((src >= 0) & (host != src))
        land = {int(v): k * interval + R["vm_ram"][v]
                / (R["host_bw"][host[v]] / 16.0) for v in moved}
        moves += [(k, int(v), int(src[v]), int(host[v])) for v in moved]
        # the interval up to the next tick, one event, integrated in
        # pieces split where moves land
        stops = sorted(set(land.values())) + [(k + 1) * interval]
        n_events += 1
        t = k * interval
        for t1 in stops:
            dt = dtype(t1 - t)
            mig = np.zeros(V, bool)
            mig[[v for v, e in land.items() if e > t]] = True
            res = host >= 0
            at = np.where(mig, src, host)
            D10 = np.zeros(N, dtype)
            n_on = np.zeros(N, np.int64)
            np.add.at(D10, at[res], (np.where(mig, 9, 10) * d)[res])
            np.add.at(D10, host[mig], d[mig])
            np.add.at(n_on, at[res], 1)
            np.add.at(n_on, host[mig], 1)
            on = n_on > 0
            u = np.minimum(D10 / (10 * cap), 1).astype(dtype)
            x = 10 * u
            j = np.minimum(np.floor(x), 9).astype(np.int64)
            w = R["watts"].astype(dtype)
            r = np.arange(N)
            watts = w[r, j] + (w[r, j + 1] - w[r, j]) * (x - j)
            # masks as 0/1 factors: np.where of a bfloat16 scalar is not safe
            on_f = on.astype(dtype)
            energy += on_f * watts * dt
            t_full += (on & (D10 >= 10 * cap)).astype(dtype) * dt
            t_active += on_f * dt
            req += res.astype(dtype) * d * dt
            scale = np.minimum(1, 10 * cap / np.maximum(D10, 1))
            grant = 0.9 * d * scale[np.clip(at, 0, N - 1)]
            short += mig.astype(dtype) * (d - grant) * dt
            t = t1

    ever = t_active > 0
    slatah = float(np.sum(t_full[ever] / t_active[ever]) / max(ever.sum(), 1))
    pdm = float(np.sum(np.where(req > 0, short / np.where(req > 0, req, 1), 0))
                / V)
    e_kwh = float(np.sum(energy) / 3.6e6)
    return {"moves": moves, "n_events": n_events, "n_migrations": len(moves),
            "energy_kwh": e_kwh, "slatah": slatah, "pdm": pdm,
            "slav": slatah * pdm, "esv": e_kwh * slatah * pdm,
            "n_overloaded": n_over, "n_place_tries": tries,
            "failed": int((host < 0).sum())}


def simulate_rows(R: dict, dtype=np.float64) -> dict:
    """Every row: per-row arrays of each number, ``moves`` as lists."""
    out = [simulate_row(R, i, dtype) for i in range(len(R["detector"]))]
    keys = [k for k in out[0] if k != "moves"]
    res = {k: np.array([o[k] for o in out]) for k in keys}
    res["moves"] = [o["moves"] for o in out]
    res["power.energy_kwh"] = res["energy_kwh"]
    res["power.esv"] = res["esv"]
    return res
