"""Kernel micro-benchmarks: wall time of the jnp reference vs the Pallas
kernel in interpret mode is NOT meaningful on CPU; this bench reports
reference-path timings (the oracle is the deployable CPU path) plus
correctness deltas, and serves as the harness that would time the compiled
kernels on TPU."""
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Timer, emit
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.ssd_scan.ref import ssd_ref


def _time(f, *args, iters=5):
    f(*args)[0].block_until_ready() if isinstance(f(*args), tuple) else \
        jax.block_until_ready(f(*args))
    t0 = time.time()
    for _ in range(iters):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / iters * 1e6


def run():
    r = np.random.default_rng(0)
    # flash attention ref at 2k
    q = jnp.asarray(r.standard_normal((1, 8, 2048, 64)).astype(np.float32))
    f = jax.jit(lambda q: attention_ref(q, q, q))
    emit("kernel/attention_ref_2k", _time(f, q), "prefill_attention")

    # ssd ref at 2k
    x = jnp.asarray(r.standard_normal((1, 2048, 8, 64)).astype(np.float32))
    b = jnp.asarray(r.standard_normal((1, 2048, 64)).astype(np.float32))
    dt = jnp.abs(jnp.asarray(r.standard_normal((1, 2048, 8)).astype(np.float32))) * .1
    a = -jnp.abs(jnp.asarray(r.standard_normal(8).astype(np.float32))) - .1
    f = jax.jit(lambda x, b, dt, a: ssd_ref(x, b, b, dt, a))
    emit("kernel/ssd_ref_2k", _time(f, x, b, dt, a), "ssd_scan")


if __name__ == "__main__":
    run()
