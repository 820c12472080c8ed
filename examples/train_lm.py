"""End-to-end LM training driver: data pipeline -> sharded model -> AdamW ->
fault-tolerant loop with checkpointing.

Default preset trains a ~25M-param model long enough to see the loss fall on
CPU; `--preset 100m --steps 300` is the paper-brief configuration (suitable
for a real accelerator or a patient CPU).

    PYTHONPATH=src python examples/train_lm.py [--steps 120] [--preset small]
"""
import argparse
import dataclasses

import jax

from repro.compile_cache import enable_compile_cache
from repro.configs.base import AttnCfg, ModelConfig
from repro.models import build_model, count_params
from repro.train.data import DataConfig, SyntheticDataset
from repro.train.elastic import SimulatedFailures
from repro.train.loop import LoopConfig, train_loop
from repro.train.optimizer import adamw, cosine_schedule
from repro.train.train_step import make_train_step

PRESETS = {
    "small": dict(n_layers=4, d_model=384, d_ff=1536, vocab=4096,
                  heads=6, kv=2, seq=128, batch=8),
    "100m": dict(n_layers=12, d_model=768, d_ff=3072, vocab=16384,
                 heads=12, kv=4, seq=512, batch=8),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="small", choices=PRESETS)
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--inject-failure", action="store_true",
                    help="kill the loop mid-run to demo checkpoint restart")
    args = ap.parse_args()
    p = PRESETS[args.preset]

    cfg = ModelConfig(
        name=f"lm-{args.preset}", family="dense",
        n_layers=p["n_layers"], d_model=p["d_model"], d_ff=p["d_ff"],
        vocab=p["vocab"],
        attn=AttnCfg(n_heads=p["heads"], n_kv=p["kv"],
                     head_dim=p["d_model"] // p["heads"]),
        vocab_pad_to=128, remat="none",
    )
    model = build_model(cfg)
    params, roles = model.init(jax.random.PRNGKey(0))
    print(f"model: {count_params(cfg)/1e6:.1f}M params")

    opt = adamw(cosine_schedule(3e-3, warmup=20, total=args.steps),
                weight_decay=0.01, grad_clip=1.0)
    step = jax.jit(make_train_step(model, opt, microbatches=2))
    data = SyntheticDataset(DataConfig(vocab=cfg.vocab, seq=p["seq"],
                                       global_batch=p["batch"]))
    failures = SimulatedFailures(fail_at=(args.steps // 2,)) \
        if args.inject_failure else None
    res = train_loop(step, params, opt.init(params), data,
                     LoopConfig(total_steps=args.steps, checkpoint_every=40,
                                checkpoint_dir=args.ckpt_dir, log_every=10),
                     failures=failures)
    print(f"done: loss {res['losses'][0]:.3f} -> {res['losses'][-1]:.3f} "
          f"({res['restarts']} restarts, {res['stragglers']} stragglers)")


if __name__ == "__main__":
    enable_compile_cache()
    main()
