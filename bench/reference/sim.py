"""Plain reference simulator: CloudSim's two-level scheduling in numpy.

A straightforward event loop over a batch of independent rows (one row per
scenario), written from the CloudSim paper (arXiv:0907.4878, sections 3.2
and 4) and nothing of the code under test.  Each pass of the loop is one
event batch:

1. VMs whose every task has finished are destroyed and give their host
   resources back.
2. Due VM requests are placed in request order on the first host with
   enough free RAM, storage and bandwidth, and enough free cores where the
   provisioner reserves cores; without core reservation a VM may share an
   already busy host.  A request no host can take is rejected.
3. Host level: space-shared grants each VM its cores whole, first come
   first served in request order; time-shared scales every VM on a host by
   ``min(1, host capacity / demand)``.
4. VM level: space-shared runs tasks whole on the VM's cores, first come
   first served in submission order; time-shared gives every ready task an
   equal share ``vm_mips / max(demanded cores, vm cores)`` per core.
5. The clock jumps to the earliest of: a task completion, a task becoming
   ready (submission plus stage-in), a VM request, the horizon.  A task
   finishes in that step when its remaining work is within
   ``1e-5 * length + 0.25`` MI of zero, the finish tolerance the
   configuration states.  Times that agree to ``SAME_TIME`` (relative) are
   one instant: in exact arithmetic a task submitted at 1200 s with 3 ms of
   stage-in becomes ready exactly when one that became ready at 3 ms and
   ran 1200 s finishes, and float64 rounding must not split that event.

Every time is computed in ``dtype`` (float64 for the reference; the
control runs the same loop in bfloat16).
"""
from __future__ import annotations

import numpy as np

SPACE_SHARED = 0
TIME_SHARED = 1
SAME_TIME = 1e-9


def rows_from_config(config: dict, params: dict) -> dict:
    """The deployment of ``config`` as ``[B, ...]`` numpy arrays, one row per
    entry of ``params`` (``host_policy``, ``vm_policy``, ``length_scale``,
    each ``[B]``)."""
    dep = config["deployment"]
    h, v, k, pol = dep["hosts"], dep["vms"], dep["tasks"], dep["policy"]
    B = len(params["length_scale"])
    H, V, C = h["count"], v["count"], k["count"]
    i = np.arange(C)
    if k["binding"] == "round_robin":
        cl_vm = i % V
    elif k["binding"] == "contiguous":
        cl_vm = i // (C // V)
    else:
        raise ValueError(f"unknown task binding {k['binding']!r}")
    submit = (i // k["group_size"]) * float(k["group_interval_s"])

    def full(n, x):
        return np.full((B, n), x, np.float64)

    scale = np.asarray(params["length_scale"], np.float64)[:, None]
    return {
        "host_cores": full(H, h["cores"]), "host_mips": full(H, h["mips"]),
        "host_ram": full(H, h["ram_mb"]), "host_storage": full(H, h["storage_mb"]),
        "host_bw": full(H, h["bw_mbps"]),
        "vm_cores": full(V, v["cores"]), "vm_mips": full(V, v["mips"]),
        "vm_ram": full(V, v["ram_mb"]), "vm_storage": full(V, v["storage_mb"]),
        "vm_bw": full(V, v["bw_mbps"]), "vm_request_t": full(V, v["request_t"]),
        "cl_vm": np.broadcast_to(cl_vm, (B, C)).copy(),
        "cl_cores": full(C, k["cores"]),
        "cl_length": full(C, k["length_mi"]) * scale,
        "cl_submit": np.broadcast_to(submit, (B, C)).astype(np.float64),
        "cl_input_mb": full(C, k["input_mb"]),
        "host_policy": np.asarray(params["host_policy"], np.int64),
        "vm_policy": np.asarray(params["vm_policy"], np.int64),
        "core_reserving": np.full(B, bool(pol["core_reserving"])),
        "horizon": np.full(B, float(pol["horizon_s"])),
    }


def simulate_rows(rows: dict, dtype=np.float64) -> dict:
    """Run every row to its end.  Returns per-row ``start_t``/``finish_t``
    ``[B, C]`` (inf: never), ``n_finished``, ``n_events`` (event batches in
    exact arithmetic), ``event_slack`` (the extra batches a rounding
    implementation may split off), ``mean_turnaround`` and ``makespan``,
    times as float64."""
    ft = np.dtype(dtype)

    def f(x):
        return np.asarray(x).astype(ft)

    def sel(cond, a, b):
        # a selection rounds nothing, so it is made in float64 (numpy's
        # ``where`` on bfloat16 operands is unreliable)
        return np.where(cond, np.asarray(a, np.float64),
                        np.asarray(b, np.float64)).astype(ft)

    B, H = rows["host_cores"].shape
    V = rows["vm_cores"].shape[1]
    C = rows["cl_vm"].shape[1]
    bi = np.arange(B)
    inf = f(np.inf)

    host_cores, host_mips = f(rows["host_cores"]), f(rows["host_mips"])
    vm_cores, vm_mips = f(rows["vm_cores"]), f(rows["vm_mips"])
    vm_need = {k: rows[f"vm_{k}"] for k in ("ram", "storage", "bw", "cores")}
    free = {k: rows[f"host_{k}"].astype(np.float64).copy()
            for k in ("ram", "storage", "bw", "cores")}
    cl_vm = rows["cl_vm"]
    cl_cores = rows["cl_cores"]
    length = f(rows["cl_length"])
    submit = f(rows["cl_submit"])
    stage = np.where(rows["cl_input_mb"] > 0,
                     rows["cl_input_mb"]
                     / np.maximum(rows["vm_bw"][bi[:, None], cl_vm], 1e-6), 0.0)
    ready_t = submit + f(stage)
    eps = f(1e-5) * length + f(0.25)
    time_host = rows["host_policy"] == TIME_SHARED
    time_vm = rows["vm_policy"] == TIME_SHARED
    onehot = cl_vm[:, :, None] == np.arange(V)[None, None, :]      # [B, C, V]
    earlier = np.arange(V)[:, None] < np.arange(V)[None, :]        # [u, v]: u < v
    # the same safety budget on event batches as the engine's default
    limit = 4 * (C + V) + 260

    t = np.zeros(B, ft)
    step = np.zeros(B, np.int64)
    slack = np.zeros(B, np.int64)
    placed = np.zeros((B, V), bool)
    failed = np.zeros((B, V), bool)
    released = np.zeros((B, V), bool)
    host_of = np.full((B, V), -1, np.int64)
    avail = np.full((B, V), inf, ft)
    rem = length.copy()
    started = np.zeros((B, C), bool)
    start_t = np.full((B, C), inf, ft)
    finish_t = np.full((B, C), inf, ft)

    def per_vm(x):                       # [B, C] -> [B, V] sum over tasks
        return (np.asarray(x, np.float64)[:, :, None] * onehot).sum(1)

    def reached(now, x):                 # now >= x, to SAME_TIME
        return now >= x - f(SAME_TIME) * np.maximum(np.abs(x), f(1.0))

    def vm_drained():
        fin = (finish_t < inf).astype(np.float64)
        has_work = per_vm(np.ones((B, C))) > 0
        return has_work & (per_vm(1.0 - fin) == 0)

    while True:
        fin = finish_t < inf
        doomed = failed[bi[:, None], cl_vm]
        live = ((step < limit) & (t < rows["horizon"])
                & ~np.all(fin | doomed, axis=1))
        if not live.any():
            break

        # 1. destroy drained VMs, return their resources
        newly = vm_drained() & placed & ~released & live[:, None]
        for b, v in zip(*np.nonzero(newly)):
            for k in free:
                free[k][b, host_of[b, v]] += vm_need[k][b, v]
        released |= newly

        # 2. place due VM requests in request order, first fit
        for v in range(V):
            due = (live & reached(t, f(rows["vm_request_t"][:, v]))
                   & ~placed[:, v] & ~failed[:, v])
            if not due.any():
                continue
            feas = np.ones((B, H), bool)
            for k in ("ram", "storage", "bw"):
                feas &= free[k] >= vm_need[k][:, v, None]
            slot = feas & (free["cores"] >= vm_need["cores"][:, v, None])
            stack = feas & ~rows["core_reserving"][:, None]
            has_slot, has_stack = slot.any(1), stack.any(1)
            h = np.where(has_slot, slot.argmax(1), stack.argmax(1))
            found = due & (has_slot | has_stack)
            failed[due & ~found, v] = True
            placed[found, v] = True
            host_of[found, v] = h[found]
            avail[found, v] = t[found]
            for k in free:
                free[k][bi[found], h[found]] -= vm_need[k][found, v]

        # 3. host level: MIPS granted to each VM
        occupying = placed & ~vm_drained()
        usable = occupying & reached(t[:, None], avail)
        hs = np.clip(host_of, 0, H - 1)
        hc, hm = host_cores[bi[:, None], hs], host_mips[bi[:, None], hs]
        same = ((host_of[:, :, None] == host_of[:, None, :])
                & occupying[:, :, None] & occupying[:, None, :])    # [B, u, v]
        prefix = ((same & earlier) * rows["vm_cores"][:, :, None]).sum(1)
        fits = prefix + rows["vm_cores"] <= rows["host_cores"][bi[:, None], hs] + 1e-6
        space = sel(usable & fits, vm_cores * np.minimum(vm_mips, hm), 0.0)
        demand = sel(occupying, vm_cores * vm_mips, 0.0)
        total = (same * demand[:, :, None]).sum(1, dtype=ft)
        scale = sel(total > 0,
                    np.minimum(f(1.0), hc * hm / np.maximum(total, f(1e-9))), 0.0)
        shared = sel(usable, vm_cores * vm_mips * scale, 0.0)
        grant = sel(time_host[:, None], shared, space)

        # 4. VM level: per-core MIPS of each task
        occ = reached(t[:, None], ready_t) & ~(finish_t < inf)
        occ_cores = np.where(occ, cl_cores, 0.0)
        cum = np.cumsum(occ_cores[:, :, None] * onehot, axis=1)      # [B, C, V]
        prefix_c = cum[bi[:, None], np.arange(C)[None, :], cl_vm] - occ_cores
        vmc = rows["vm_cores"][bi[:, None], cl_vm]
        fits_c = prefix_c + cl_cores <= vmc + 1e-6
        percore = grant / np.maximum(vm_cores, f(1.0))
        space_c = sel(occ & fits_c, percore[bi[:, None], cl_vm], 0.0)
        denom = np.maximum(per_vm(occ_cores), rows["vm_cores"])
        share = grant / f(np.maximum(denom, 1e-9))
        time_c = sel(occ, share[bi[:, None], cl_vm], 0.0)
        rate = sel(time_vm[:, None], time_c, space_c)
        rate = sel(grant[bi[:, None], cl_vm] > 0, rate, 0.0)
        active = rate > 0

        # 5. next event: completion, readiness, VM request, horizon
        unready = ~reached(t[:, None], ready_t)
        unplaced = ~placed & ~failed
        migrating = placed & ~reached(t[:, None], avail)
        bound = np.minimum.reduce([
            sel(unready, ready_t, np.inf).min(1),
            sel(unplaced, f(rows["vm_request_t"]), np.inf).min(1),
            sel(migrating, avail, np.inf).min(1),
            f(rows["horizon"]),
        ])
        bound_dt = np.maximum(bound - t, f(0.0))
        with np.errstate(divide="ignore", invalid="ignore"):
            dt_fin = sel(active, rem / sel(active, rate, 1.0), np.inf)
        dt = np.minimum(dt_fin.min(1), bound_dt)
        new_rem = sel(active, np.maximum(rem - rate * dt[:, None], f(0.0)), rem)

        # 6. commit the live rows
        go = live[:, None]
        newly_started = active & ~started & go
        newly_fin = active & (new_rem <= eps) & go
        start_t = sel(newly_started, t[:, None], start_t)
        started |= newly_started
        t_next = t + dt
        finish_t = sel(newly_fin, t_next[:, None], finish_t)
        rem = sel(go, sel(newly_fin, 0.0, new_rem), rem)
        # an instant where kinds of event meet in exact arithmetic (a
        # completion with a readiness, say) may split into one event batch
        # per kind under float32 rounding: count the extra batches allowed
        kinds = (newly_fin.any(1).astype(np.int64)
                 + (reached(t_next[:, None], ready_t) & unready).any(1)
                 + (reached(t_next[:, None], f(rows["vm_request_t"]))
                    & unplaced).any(1)
                 + (reached(t_next[:, None], avail) & migrating).any(1))
        slack = slack + np.where(live, np.maximum(kinds - 1, 0), 0)
        t = sel(live, t_next, t)
        step = step + live

    fin = finish_t < inf
    s64, f64 = start_t.astype(np.float64), finish_t.astype(np.float64)
    n_fin = fin.sum(1)
    tat = np.where(fin, f64 - rows["cl_submit"], 0.0)
    return {
        "start_t": np.where(started, s64, np.inf),
        "finish_t": np.where(fin, f64, np.inf),
        "n_finished": n_fin,
        "n_events": step,
        "event_slack": slack,
        "mean_turnaround": tat.sum(1) / np.maximum(n_fin, 1),
        "makespan": np.where(fin, f64, -np.inf).max(1),
    }
