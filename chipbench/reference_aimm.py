"""The plain reference of an AIMM lane: the simulator's epoch cost model
with the AIMM mapper applying given actions, for one scenario and one
episode at a time, as a NumPy float32 loop over epochs.

An independent reference cannot follow a learned agent: a last-bit
difference in its arithmetic changes an action sooner or later.  So the
program reports, per epoch, the action it applied (`action_t`) and the
cube a data or compute remap targeted (`target_t`, drawn at random for a
"near" remap), and this reference replays them on its own copy of the
environment.  It checks that every recorded target is one the action may
take, and counts those that are not (`illegal_actions`).

It imports the baseline reference's mesh, scheduling and energy layout
(`chipbench/reference.py`) and nothing of the program.  The documented
semantics it follows, beyond the baseline epoch (see `reference.py`):

* cadence: the agent is invoked at an epoch when at least `level` epochs
  passed since its last invocation (stride = level + 1); the interval
  level starts at 0, INC/DEC move it by one within 0..3, and an
  invocation adds `t_agent` cycles to its epoch;
* hot page: the page the window touches most (dest, src1 and src2 counted
  alike, the lowest page id on a tie), skipping the `recent_ring` pages
  the last invocations acted on; its compute cube is that of the first op
  touching it (of op 0 when none does);
* targets: NEAR_* a mesh neighbour of that compute cube, FAR_* its mirror
  through the mesh centre, SOURCE_COMPUTE the value C ("source mode"),
  and no target (`NO_TARGET`) for DEFAULT, INC and DEC or outside an
  invocation;
* data remap: the page moves to the target cube unless it is there.  The
  move costs latency = page flits + hops x t_router + t_page_walk, a
  stall of 0.25 (read-write page) or 0.05 (read-only) of that latency +
  4 x min(window touches of the page, 8), and the page's flits on every
  link of the XY route; stall and link loads are charged to the next
  epoch, and the new mapping serves from the next epoch;
* compute remap: the page's entry in the remap table becomes the target,
  DEFAULT clears it; an op takes the first entry set among its dest,
  src1 and src2 pages (C: src1's cube) over the technique's choice.  An
  entry's age counts the epochs it has been set (a new target keeps the
  age), and an entry older than `remap_ttl` is cleared;
* statistics: a move counts in `migrations`, its page in
  `pages_migrated`, and every access of the window to a moved page
  (including the page moved in this epoch) in `access_on_migrated`;
  energy adds 2 migration-queue and page-flits MDMA accesses and the
  page's bits twice over the network per move, 2 state-buffer accesses
  per invocation and, for a learned lane, 1 + 3 x batch weight and
  1 + batch replay accesses per invocation."""
from __future__ import annotations

import numpy as np

from chipbench.reference import EMA_DECAY, EN, Mesh, schedule

f32 = np.float32
DEFAULT, NEAR_DATA, FAR_DATA, NEAR_COMPUTE, FAR_COMPUTE, SOURCE_COMPUTE, \
    INC_INTERVAL, DEC_INTERVAL = range(8)
N_INTERVALS = 4
NO_TARGET = 255              # `target_t` where no remap applied


def _legal(action: int, target: int, invoke: bool, ccube: int,
           mesh: Mesh) -> bool:
    if not invoke:
        return action == DEFAULT and target == NO_TARGET
    if action in (NEAR_DATA, NEAR_COMPUTE):
        return 0 <= target < mesh.C and mesh.hops(ccube, target) == 1
    if action in (FAR_DATA, FAR_COMPUTE):
        far = (mesh.Y - 1 - mesh.y[ccube]) * mesh.X + (mesh.X - 1
                                                       - mesh.x[ccube])
        return target == far
    if action == SOURCE_COMPUTE:
        return target == mesh.C
    return action in (DEFAULT, INC_INTERVAL, DEC_INTERVAL) \
        and target == NO_TARGET


def episode(trace, technique: str, cfg: dict, action_t, target_t,
            learned: bool, batch_size: int, cycles_dtype=f32) -> dict:
    """One episode of one AIMM scenario under the recorded `action_t` /
    `target_t` (one entry per epoch).  `learned` charges the DQN's weight
    and replay energy; `cycles_dtype` is the precision each epoch's cycle
    count is kept in.  Returns the statistics of `run_grid`'s result and
    `illegal_actions`."""
    mesh = Mesh(cfg)
    C, P, W = mesh.C, trace.n_pages, cfg["epoch_ops"]
    n_ops = trace.n_ops
    n_epochs = -(-n_ops // W)
    pflits = cfg["packet_bytes"] / cfg["link_bytes_per_cycle"]
    page_flits = f32(cfg["page_bytes"] / cfg["link_bytes_per_cycle"])
    rw_page = np.asarray(trace.read_write, bool)
    table = (np.arange(P) % C).astype(np.int64)
    remap = np.full(P, -1, np.int64)
    age = np.zeros(P, np.int64)
    moved_mask = np.zeros(P, bool)
    recent = [-1] * max(cfg["recent_ring"], 1)
    level, since = 0, 0
    ema = np.zeros(P, f32)
    m_hot = P - (int(P * (1 - cfg["pei_hot_frac"])) - 1) % P
    stall = f32(0)
    pending = np.zeros(mesh.n_links, f32)
    st = dict(cycles=f32(0), ops=f32(0), hops_sum=f32(0), util_sum=f32(0),
              epochs=f32(0), access_total=f32(0), migrations=f32(0),
              access_on_migrated=f32(0))
    energy = np.zeros(len(EN), f32)
    opc_t = np.zeros(n_epochs, f32)
    valid_t = np.zeros(n_epochs, np.uint16)
    invoke_t = np.zeros(n_epochs, np.uint16)
    illegal = 0
    mem_bits = cfg["packet_bytes"] * 8

    for e in range(n_epochs):
        sl = slice(e * W, min((e + 1) * W, n_ops))
        dest, src1, src2 = trace.dest[sl], trace.src1[sl], trace.src2[sl]
        nv = f32(len(dest))
        dc, s1c, s2c = table[dest], table[src1], table[src2]

        hot1 = hot2 = None
        if technique == "pei":
            thresh = max(np.sort(ema)[::-1][m_hot - 1], f32(1e-6))
            hot1, hot2 = ema[src1] >= thresh, ema[src2] >= thresh
        cc = schedule(technique, dc, s1c, s2c, hot1, hot2)
        entry = np.where(remap[dest] >= 0, remap[dest],
                         np.where(remap[src1] >= 0, remap[src1],
                                  remap[src2]))
        cc = np.where(entry == C, s1c, np.where(entry >= 0, entry, cc))
        ema = EMA_DECAY * ema
        for pages in (dest, src1, src2):
            np.add.at(ema, pages, f32(1))

        loads = mesh.link_loads(np.concatenate([s1c, s2c, cc]),
                                np.concatenate([cc, cc, dc]),
                                pflits) + pending
        op_hops = (mesh.hops(s1c, cc) + mesh.hops(s2c, cc)
                   + mesh.hops(cc, dc))
        hops_total = f32(op_hops.sum())
        mean_hops = hops_total / max(nv, f32(1))

        ops_c = np.bincount(cc, minlength=C).astype(f32)
        pages = np.concatenate([dest, src1, src2])
        acc_c = np.bincount(table[pages], minlength=C).astype(f32)
        distinct = np.unique(pages)
        distinct_c = np.bincount(table[distinct], minlength=C).astype(f32)

        table_excess = np.maximum(ops_c - f32(cfg["nmp_table_size"]),
                                  f32(0)).sum(dtype=f32)
        compute_serial = (ops_c.max() * f32(cfg["t_op"])
                          / f32(cfg["cube_issue_rate"]))
        util = (f32(ops_c.sum()) ** 2 / max(f32((ops_c ** 2).sum()), f32(1))
                / f32(C))
        hit_c = np.where(acc_c > 0,
                         f32(1) - distinct_c / np.maximum(acc_c, f32(1)),
                         f32(0.5)).astype(f32)
        lat_c = (hit_c * f32(cfg["t_dram_hit"])
                 + (f32(1) - hit_c) * f32(cfg["t_dram_miss"]))
        dram_serial = ((acc_c * lat_c).max()
                       / f32(cfg["n_vaults"] * 4.0))
        mc_inject = nv / f32(cfg["n_mcs"] * cfg["mc_issue_rate"])
        mean_load = loads.sum(dtype=f32) / f32(mesh.n_links)
        imbalance = loads.max() / max(mean_load, f32(1))
        link_serial = loads.max() * (
            f32(1) + f32(cfg["congestion_alpha"] - 1.0)
            * np.clip((imbalance - f32(1)) / f32(4), f32(0), f32(1)))
        mean_lat = (mean_hops * f32(cfg["t_router"]) + f32(pflits)
                    + (acc_c * lat_c).sum(dtype=f32)
                    / max(acc_c.sum(dtype=f32), f32(1)))
        invoke = since >= level
        cycles = ((f32(cfg["t_agent"]) if invoke else f32(0)) + mc_inject
                  + max(compute_serial, link_serial, dram_serial)
                  + mean_lat + table_excess * f32(cfg["t_op"]) + stall)
        cycles = f32(np.asarray(cycles, f32).astype(cycles_dtype))
        opc = nv / max(cycles, f32(1))

        # the hot page and its compute cube
        touch = np.bincount(pages, minlength=P)
        touch[[p for p in recent if p >= 0]] = 0
        hot = int(np.argmax(touch))
        touches = int(np.bincount(pages, minlength=P)[hot])
        on_hot = (dest == hot) | (src1 == hot) | (src2 == hot)
        ccube = int(cc[np.argmax(on_hot)])

        # the recorded action
        a, tg = int(action_t[e]), int(target_t[e])
        ok = _legal(a, tg, invoke, ccube, mesh)
        illegal += not ok
        moved = False
        stall = f32(0)
        pending = np.zeros(mesh.n_links, f32)
        if invoke and ok:
            if a in (NEAR_DATA, FAR_DATA) and tg != table[hot]:
                old, moved = int(table[hot]), True
                hops = f32(mesh.hops(old, tg))
                latency = (page_flits + hops * f32(cfg["t_router"])
                           + f32(cfg["t_page_walk"]))
                frac = f32(0.25) if rw_page[hot] else f32(0.05)
                stall = frac * latency + f32(4) * f32(min(touches, 8))
                pending = mesh.link_loads(np.array([old]), np.array([tg]),
                                          float(page_flits))
                table[hot] = tg
            elif a in (NEAR_COMPUTE, FAR_COMPUTE, SOURCE_COMPUTE):
                remap[hot] = tg
            elif a == DEFAULT:
                remap[hot] = -1
            elif a == INC_INTERVAL:
                level = min(level + 1, N_INTERVALS - 1)
            elif a == DEC_INTERVAL:
                level = max(level - 1, 0)
        if invoke:
            recent = recent[1:] + [hot]
        age = np.where(remap >= 0, age + 1, 0)
        remap = np.where(age > cfg["remap_ttl"], -1, remap)
        age = np.where(age > cfg["remap_ttl"], 0, age)
        since = 0 if invoke else since + 1
        if moved:
            moved_mask[hot] = True
        acc_mig = f32(moved_mask[pages].sum())

        inv = f32(invoke)
        mv = f32(moved)
        energy[EN.index("mem_bits")] += nv * 3 * mem_bits
        energy[EN.index("page_cache")] += 2 * nv
        energy[EN.index("nmp_buf")] += 2 * nv
        energy[EN.index("mig_q")] += 2 * mv
        energy[EN.index("mdma")] += mv * page_flits
        energy[EN.index("state_buf")] += 2 * inv
        if learned:
            energy[EN.index("weight")] += (1 + 3 * batch_size) * inv
            energy[EN.index("replay")] += (1 + batch_size) * inv
        energy[EN.index("net_bit_hops")] += (
            hops_total * mem_bits + mv * f32(cfg["page_bytes"] * 8 * 2))

        st["cycles"] += cycles
        st["ops"] += nv
        st["hops_sum"] += hops_total
        st["util_sum"] += util
        st["epochs"] += f32(1)
        st["access_total"] += 3 * nv
        st["migrations"] += mv
        st["access_on_migrated"] += acc_mig
        opc_t[e] = opc
        valid_t[e] = len(dest)
        invoke_t[e] = invoke

    return dict(st, pages_migrated=f32(moved_mask.sum()), energy=energy,
                opc_t=opc_t, valid_t=valid_t, invoke_t=invoke_t,
                illegal_actions=illegal)


def scenario(trace, technique: str, episodes: int, cfg: dict, action_t,
             target_t, learned: bool, batch_size: int,
             cycles_dtype=f32) -> dict:
    """Every episode of one AIMM scenario, stacked as `run_grid` returns
    them; `action_t` / `target_t` are (episodes, n_epochs) or longer.  The
    environment starts afresh each episode; what an agent carries across
    episodes reaches it only through the recorded actions."""
    n_epochs = -(-trace.n_ops // cfg["epoch_ops"])
    eps = [episode(trace, technique, cfg, action_t[e][:n_epochs],
                   target_t[e][:n_epochs], learned, batch_size, cycles_dtype)
           for e in range(episodes)]
    return {k: np.stack([np.asarray(ep[k]) for ep in eps]) for k in eps[0]}
