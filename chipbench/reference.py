"""The plain reference: the simulator's epoch cost model for one scenario,
written from its documented semantics as a NumPy float32 loop over epochs.

It covers the mappers that run no agent (`none`, `tom`) under the three
NMP techniques (`bnmp`, `ldb`, `pei`) on a 2D mesh with XY routing, the
paper's system (AIMM, arXiv 2104.13671, Table 1 and section 6.3).  It
shares nothing with the program: no batching, padding, vmap or feature
flags, one scenario and one epoch at a time.

One epoch takes the next `epoch_ops` ops of the trace (`dest += src1 OP
src2`, page ids) and:

* maps pages to cubes: round-robin `page % C`, or under TOM the committed
  candidate mapping;
* schedules each op's compute cube: BNMP at `dest`'s cube, LDB at `src1`'s,
  PEI by the operands' CPU-cache hits (a source page is hot when its
  access EMA, before this epoch's update, reaches the m-th largest EMA,
  m = the top 5% of the trace's pages);
* routes three packets per op (src1 -> compute, src2 -> compute,
  compute -> dest), 4 flits each, X first at the source row, then Y at the
  destination column, and sums each link's flits;
* times the epoch: MC injection + the slowest of compute, the hottest
  link (amplified by its imbalance over the mean link) and DRAM (row-buffer
  hits from distinct pages per cube), + mean packet latency + NMP-table
  overflow + the previous epoch's migration stall;
* TOM: each period of 6 + 8 epochs scores candidate k (pages grouped by
  2^k, round-robin over cubes) on epoch k's window, and at epoch 6 commits
  the best one, paying for the pages it moves.

Statistics are those of `run_grid`'s result, one row per episode."""
from __future__ import annotations

import numpy as np

f32 = np.float32
TOM_CANDIDATES = 6           # stride 2^k groupings, k = 0..5
TOM_COMMIT_EPOCHS = 8        # epochs the winner runs before re-profiling
EMA_DECAY = f32(0.9)         # PEI page-access EMA
EN = ("page_cache", "nmp_buf", "mig_q", "mdma", "weight", "replay",
      "state_buf", "net_bit_hops", "mem_bits")


class Mesh:
    """A 2D mesh of X x Y cubes with static XY routing."""

    def __init__(self, cfg: dict):
        self.X, self.Y = cfg["mesh_x"], cfg["mesh_y"]
        self.C = self.X * self.Y
        self.n_links = self.Y * (self.X - 1) + self.X * (self.Y - 1)
        self.x = np.arange(self.C) % self.X
        self.y = np.arange(self.C) // self.X

    def hops(self, a, b):
        return np.abs(self.x[a] - self.x[b]) + np.abs(self.y[a] - self.y[b])

    def link_loads(self, src, dst, flits: float) -> np.ndarray:
        """Flits on every link (horizontal links first, then vertical) for
        packets src -> dst of `flits` each."""
        X, Y = self.X, self.Y
        h = np.zeros((Y, X), np.int64)       # row y, link x <-> x+1
        sx, sy, dx, dy = self.x[src], self.y[src], self.x[dst], self.y[dst]
        np.add.at(h, (sy, np.minimum(sx, dx)), 1)
        np.add.at(h, (sy, np.maximum(sx, dx)), -1)
        v = np.zeros((X, Y), np.int64)       # column x, link y <-> y+1
        np.add.at(v, (dx, np.minimum(sy, dy)), 1)
        np.add.at(v, (dx, np.maximum(sy, dy)), -1)
        counts = np.concatenate([np.cumsum(h, 1)[:, :X - 1].ravel(),
                                 np.cumsum(v, 1)[:, :Y - 1].ravel()])
        return (counts * flits).astype(f32)


def tom_candidates(n_pages: int, n_cubes: int) -> np.ndarray:
    pages = np.arange(n_pages)
    return np.stack([(pages >> k) % n_cubes for k in range(TOM_CANDIDATES)])


def tom_score(mapping, dest, src1, src2, n_cubes: int) -> f32:
    """Operand co-location share less half the load imbalance."""
    d, a, b = mapping[dest], mapping[src1], mapping[src2]
    n = f32(max(len(dest), 1))
    co = ((a == d).astype(f32) + (b == d).astype(f32)) / f32(2)
    co_frac = co.sum(dtype=f32) / n
    ops_c = np.bincount(d, minlength=n_cubes).astype(f32)
    imb = ((ops_c.max() / n - f32(1 / n_cubes))
           / f32(1 - 1 / n_cubes))
    return f32(co_frac - f32(0.5) * np.clip(imb, f32(0), f32(1)))


def schedule(technique: str, dc, s1c, s2c, hot1, hot2):
    if technique == "bnmp":
        return dc
    if technique == "ldb":
        return s1c
    cc = np.where(hot1, s2c, s1c)        # offload beside the missing operand
    cc = np.where(hot1 & hot2, s1c, cc)
    return np.where(~(hot1 | hot2), dc, cc)


def episode(trace, technique: str, mapper: str, cfg: dict,
            cycles_dtype=f32) -> dict:
    """One episode (one pass over the trace) of one scenario.
    `cycles_dtype` is the precision each epoch's cycle count is kept in."""
    if mapper not in ("none", "tom"):
        raise ValueError(f"the reference runs no agent (mapper {mapper!r})")
    mesh = Mesh(cfg)
    C, P, W = mesh.C, trace.n_pages, cfg["epoch_ops"]
    n_ops = trace.n_ops
    n_epochs = -(-n_ops // W)
    pflits = cfg["packet_bytes"] / cfg["link_bytes_per_cycle"]
    page_flits = cfg["page_bytes"] / cfg["link_bytes_per_cycle"]
    table = np.arange(P) % C
    cands = tom_candidates(P, C)
    scores = np.zeros(TOM_CANDIDATES, f32)
    active = -1
    ema = np.zeros(P, f32)
    m_hot = P - (int(P * (1 - cfg["pei_hot_frac"])) - 1) % P
    stall = f32(0)
    st = dict(cycles=f32(0), ops=f32(0), hops_sum=f32(0), util_sum=f32(0),
              epochs=f32(0), access_total=f32(0))
    energy = np.zeros(len(EN), f32)
    opc_t = np.zeros(n_epochs, f32)
    valid_t = np.zeros(n_epochs, np.uint16)

    for e in range(n_epochs):
        sl = slice(e * W, min((e + 1) * W, n_ops))
        dest, src1, src2 = trace.dest[sl], trace.src1[sl], trace.src2[sl]
        nv = f32(len(dest))
        eff = cands[active] if (mapper == "tom" and active >= 0) else table
        dc, s1c, s2c = eff[dest], eff[src1], eff[src2]

        hot1 = hot2 = None
        if technique == "pei":
            thresh = max(np.sort(ema)[::-1][m_hot - 1], f32(1e-6))
            hot1, hot2 = ema[src1] >= thresh, ema[src2] >= thresh
        cc = schedule(technique, dc, s1c, s2c, hot1, hot2)
        ema = EMA_DECAY * ema
        for pages in (dest, src1, src2):
            np.add.at(ema, pages, f32(1))

        # routes: src1 -> c, src2 -> c, c -> dest
        loads = mesh.link_loads(np.concatenate([s1c, s2c, cc]),
                                np.concatenate([cc, cc, dc]), pflits)
        hops_total = f32((mesh.hops(s1c, cc) + mesh.hops(s2c, cc)
                          + mesh.hops(cc, dc)).sum())
        mean_hops = hops_total / max(nv, f32(1))

        # per-cube compute, accesses and distinct pages (row buffers)
        ops_c = np.bincount(cc, minlength=C).astype(f32)
        pages = np.concatenate([dest, src1, src2])
        acc_c = np.bincount(eff[pages], minlength=C).astype(f32)
        distinct = np.unique(pages)
        distinct_c = np.bincount(eff[distinct], minlength=C).astype(f32)

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
        cycles = (mc_inject + max(compute_serial, link_serial, dram_serial)
                  + mean_lat + table_excess * f32(cfg["t_op"]) + stall)
        cycles = f32(np.asarray(cycles, f32).astype(cycles_dtype))
        opc = nv / max(cycles, f32(1))

        # TOM: profile candidate `phase`, or commit the best
        moved = f32(0)
        stall = f32(0)
        if mapper == "tom":
            phase = e % (TOM_CANDIDATES + TOM_COMMIT_EPOCHS)
            if phase < TOM_CANDIDATES:
                scores[phase] = tom_score(cands[phase], dest, src1, src2, C)
            elif phase == TOM_CANDIDATES:
                best = int(np.argmax(scores))
                prev = cands[active] if active >= 0 else table
                moved = f32((cands[best] != prev).sum())
                active = best
                stall = moved * f32(page_flits) / f32(mesh.n_links * 8.0)

        mem_bits = cfg["packet_bytes"] * 8
        energy[EN.index("mem_bits")] += nv * 3 * mem_bits
        energy[EN.index("page_cache")] += 2 * nv
        energy[EN.index("nmp_buf")] += 2 * nv
        energy[EN.index("net_bit_hops")] += (
            hops_total * mem_bits + moved * f32(cfg["page_bytes"] * 8 * 2))

        st["cycles"] += cycles
        st["ops"] += nv
        st["hops_sum"] += hops_total
        st["util_sum"] += util
        st["epochs"] += f32(1)
        st["access_total"] += 3 * nv
        opc_t[e] = opc
        valid_t[e] = len(dest)

    return dict(st, migrations=f32(0), pages_migrated=f32(0),
                access_on_migrated=f32(0), energy=energy, opc_t=opc_t,
                valid_t=valid_t, invoke_t=(valid_t > 0).astype(np.uint16))


def scenario(trace, technique: str, mapper: str, episodes: int, cfg: dict,
             cycles_dtype=f32) -> dict:
    """Every episode of one scenario, stacked as `run_grid` returns them:
    a mapper with no agent starts each episode from the same state, so its
    episodes repeat the first."""
    one = episode(trace, technique, mapper, cfg, cycles_dtype)
    return {k: np.stack([np.asarray(v)] * episodes) for k, v in one.items()}
