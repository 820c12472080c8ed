"""Quickstart: AIMM improving an NMP workload in ~30 lines.

    PYTHONPATH=src python examples/quickstart.py [--app SPMV]
"""
import argparse

from repro.compile_cache import enable_compile_cache
from repro.nmp import NMPConfig, make_trace, run_episode, run_program
from repro.nmp.stats import summarize


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--app", default="PR", help="BP LUD KM MAC PR RBM RD SC SPMV")
    ap.add_argument("--episodes", type=int, default=5)
    args = ap.parse_args()

    cfg = NMPConfig()                       # paper Table 1: 4x4 cube mesh
    trace = make_trace(args.app, n_ops=16384)

    base = summarize(run_episode(trace, cfg, technique="bnmp", mapper="none"))
    print(f"BNMP baseline : OPC={base['opc']:.3f} cycles={base['cycles']:.0f}")

    results = run_program(trace, cfg, technique="bnmp", mapper="aimm",
                          episodes=args.episodes, seed=0)
    for i, r in enumerate(results):
        s = summarize(r)
        print(f"AIMM episode {i}: OPC={s['opc']:.3f} "
              f"speedup={base['cycles'] / s['cycles']:.2f}x "
              f"migrations={s['migrations']:.0f} "
              f"util={s['compute_util']:.2f}")
    print("(the dueling-DQN persists across episodes — the paper's "
          "continual-learning protocol)")


if __name__ == "__main__":
    enable_compile_cache()
    main()
