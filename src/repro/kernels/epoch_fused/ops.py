"""Backend dispatch for the fused epoch core.

`REPRO_EPOCH_BACKEND` selects how the epoch simulation core executes:

  auto             jnp on every platform (the default)
  jnp              the gather/einsum path, compiled by XLA (bit-exact
                   reference and the path that runs on the chip)
  pallas           the fused kernel, compiled by Mosaic on a TPU (interpret
                   mode elsewhere).  The TPU compiler currently refuses it —
                   the shared stage's row-buffer stamp scatter-max and the
                   route/TOM stages' P-indexed gathers (see kernel.py) — and
                   that error surfaces unchanged at compile time
  pallas_interpret the fused kernel forced into interpreter mode everywhere
                   (the parity oracle of the CPU tests)

The knob is validated eagerly at import AND at every resolve, raising a
ValueError that names the knob and the offending value.  The resolved
backend is carried
in `engine.BodyFlags.epoch_backend` — a static jit argument — so flipping
the env var between calls selects a distinct compiled program instead of
being silently frozen into a resident one.

Dispatchers below take the same arrays for every backend and return the
stage NamedTuples from `ref`; the topology object is passed opaquely (duck
typed) so this package never imports `repro.nmp.topology`.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels.epoch_fused import kernel, ref
from repro.kernels.epoch_fused.ref import RouteParts, SharedParts

ENV_KNOB = "REPRO_EPOCH_BACKEND"
EPOCH_BACKENDS = ("auto", "jnp", "pallas", "pallas_interpret")


def _validate_backend(mode: str, source: str) -> str:
    if mode not in EPOCH_BACKENDS:
        raise ValueError(
            f"{source}={mode!r} is not a valid epoch backend; expected one "
            f"of {EPOCH_BACKENDS} (auto = jnp on every platform; "
            f"pallas_interpret forces the kernel's interpreter mode on any "
            f"backend)")
    return mode


# Fail fast on a typo'd env knob: at import, not at first dispatch.
_validate_backend(os.environ.get(ENV_KNOB, "auto"), ENV_KNOB)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_backend(mode: str | None = None) -> str:
    """Resolve the requested mode (default: the env knob) to one of
    {jnp, pallas, pallas_interpret}; validates either source."""
    if mode is None:
        mode = _validate_backend(os.environ.get(ENV_KNOB, "auto"), ENV_KNOB)
    else:
        _validate_backend(mode, "epoch backend")
    return "jnp" if mode == "auto" else mode


def _interpret(backend: str) -> bool:
    # `pallas` off-TPU still runs (and tests) the kernel via interpret mode.
    return backend == "pallas_interpret" or not _on_tpu()


def shared_parts(dest, src1, src2, valid, epochs, rb_stamp, page_ema,
                 n_pages, pei_idx, *, pei_k: int, aimm: bool,
                 backend: str) -> SharedParts:
    """Seed-invariant stage for one lane (engine `_shared_epoch` core)."""
    if backend == "jnp":
        return ref.shared_stage(dest, src1, src2, valid, epochs, rb_stamp,
                                page_ema if pei_k > 0 else None,
                                n_pages if pei_k > 0 else None,
                                pei_idx if pei_k > 0 else None,
                                pei_k=pei_k, aimm=aimm)
    sp, _ = kernel.fused_epoch_call(
        dest, src1, src2, valid, epochs=epochs, rb_stamp=rb_stamp,
        page_ema=page_ema if pei_k > 0 else None, n_pages=n_pages,
        pei_idx=pei_idx, pei_k=pei_k, aimm=aimm, run_shared=True,
        run_route=False, interpret=_interpret(backend))
    return sp


def route_parts(dest, src1, src2, valid, rb_winner, pei_hot1, pei_hot2,
                eff_table, compute_remap, technique, is_aimm,
                pending_mig_loads, topo, *, pei_k: int, aimm: bool,
                n_mcs: int, packet_flits: float, backend: str) -> RouteParts:
    """Schedule/route/count stage for one cell (`_epoch_sim` route core)."""
    if backend == "jnp":
        return ref.route_stage(
            dest, src1, src2, valid, rb_winner, pei_hot1, pei_hot2,
            eff_table, compute_remap, technique, is_aimm, pending_mig_loads,
            jnp.asarray(topo.route_links), jnp.asarray(topo.hops),
            jnp.asarray(topo.nearest_mc), pei=pei_k > 0, aimm=aimm,
            n_mcs=n_mcs, packet_flits=packet_flits)
    _, rp = kernel.fused_epoch_call(
        dest, src1, src2, valid, rb_winner=rb_winner, pei_hot1=pei_hot1,
        pei_hot2=pei_hot2, eff_table=eff_table, compute_remap=compute_remap,
        technique=technique, is_aimm=is_aimm,
        pending_mig_loads=pending_mig_loads,
        routes_flat=jnp.asarray(topo.routes_flat),
        hops_flat=jnp.asarray(topo.hops_flat),
        nearest_mc=jnp.asarray(topo.nearest_mc), pei_k=pei_k, aimm=aimm,
        run_shared=False, run_route=True, n_mcs=n_mcs,
        packet_flits=packet_flits, interpret=_interpret(backend))
    return rp


def fused_parts(dest, src1, src2, valid, epochs, rb_stamp, page_ema,
                n_pages, pei_idx, eff_table, compute_remap, technique,
                is_aimm, pending_mig_loads, topo, *, pei_k: int, aimm: bool,
                n_mcs: int, packet_flits: float, backend: str
                ) -> tuple[SharedParts, RouteParts]:
    """Both stages in ONE kernel launch — the fully-fused per-cell path used
    when the epoch driver is not seed-sharing.  (The jnp backend never calls
    this; it runs the two ref stages inline via the dispatchers above.)"""
    assert backend != "jnp"
    sp, rp = kernel.fused_epoch_call(
        dest, src1, src2, valid, epochs=epochs, rb_stamp=rb_stamp,
        page_ema=page_ema if pei_k > 0 else None, n_pages=n_pages,
        pei_idx=pei_idx, eff_table=eff_table, compute_remap=compute_remap,
        technique=technique, is_aimm=is_aimm,
        pending_mig_loads=pending_mig_loads,
        routes_flat=jnp.asarray(topo.routes_flat),
        hops_flat=jnp.asarray(topo.hops_flat),
        nearest_mc=jnp.asarray(topo.nearest_mc), pei_k=pei_k, aimm=aimm,
        run_shared=True, run_route=True, n_mcs=n_mcs,
        packet_flits=packet_flits, interpret=_interpret(backend))
    return sp, rp


def tom_scores(dest, src1, src2, valid, cands, n_cubes: int, *,
               backend: str) -> jnp.ndarray:
    """(K,) TOM candidate scores for one lane's window."""
    if backend == "jnp":
        return ref.tom_stage(dest, src1, src2, valid, cands, n_cubes)
    return kernel.tom_scores_call(dest, src1, src2, valid, cands,
                                  n_cubes=n_cubes,
                                  interpret=_interpret(backend))
