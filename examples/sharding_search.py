"""Beyond-paper: the AIMM agent searching TPU sharding/mapping knobs.

The same continual dueling-DQN that remaps NMP pages drives microbatching,
remat policy, FSDP, int8-optimizer and expert-parallel decisions for any
assigned architecture, rewarded by the analytic roofline step time — and is
validated against exhaustive search over the knob lattice.

    PYTHONPATH=src python examples/sharding_search.py --arch qwen3-32b

Like the NMP sweep engine, the example is grid-shaped: `--arch all` (or a
comma list) sweeps the scenario grid of architectures x seeds and prints one
row per cell with the RL-vs-exhaustive optimality gap.
"""
import argparse

from repro.compile_cache import enable_compile_cache
from repro.configs import ARCHS, SHAPES, get_config
from repro.core.sharding_mapper import Knobs, exhaustive_best, search


def _fmt(t):
    return "OOM" if t == float("inf") else f"{t*1e3:.1f} ms"


def run_one(arch: str, shape_name: str, steps: int, seed: int, verbose: bool):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    res = search(cfg, shape, steps=steps, seed=seed)
    gt, gt_t = exhaustive_best(cfg, shape)
    gap = (res.best_step_s / gt_t - 1) * 100 if gt_t > 0 else 0.0
    if verbose:
        print(f"arch={arch} shape={shape_name} mesh=16x16 (256 chips)")
        print(f"  start mapping : {Knobs()}  step={_fmt(res.baseline_step_s)}")
        print(f"  RL-found      : {res.best}  step={_fmt(res.best_step_s)}")
        print(f"  exhaustive    : {gt}  step={_fmt(gt_t)}")
        print(f"  RL vs optimum : {gap:+.1f}%")
        visited = len({k for k, _ in res.trajectory})
        print(f"  ({steps} invocations, {visited} distinct mappings visited; "
              f"exhaustive sweep is {6*3*2*2*2})")
    return res, gt_t, gap


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="jamba-1.5-large-398b",
                    help="architecture, comma list, or 'all'")
    ap.add_argument("--shape", default="train_4k", choices=list(SHAPES))
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--seeds", type=int, default=1,
                    help="seeds per architecture in sweep mode")
    args = ap.parse_args()

    archs = list(ARCHS) if args.arch == "all" else args.arch.split(",")
    for a in archs:
        assert a in ARCHS, f"unknown arch {a!r} (choices: {', '.join(ARCHS)})"

    if len(archs) == 1 and args.seeds == 1:
        run_one(archs[0], args.shape, args.steps, seed=0, verbose=True)
        return

    print(f"{'arch':28s} {'seed':>4s} {'RL step':>10s} {'optimum':>10s} "
          f"{'gap':>7s}")
    for arch in archs:
        for seed in range(args.seeds):
            res, gt_t, gap = run_one(arch, args.shape, args.steps, seed,
                                     verbose=False)
            print(f"{arch:28s} {seed:4d} {_fmt(res.best_step_s):>10s} "
                  f"{_fmt(gt_t):>10s} {gap:+6.1f}%")


if __name__ == "__main__":
    enable_compile_cache()
    main()
