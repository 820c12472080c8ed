"""Partition layer of the sweep pipeline: device meshes + lane/seed sharding.

Builds a 2-D `jax.sharding.Mesh` over the available devices with axes
`("lanes", "seeds")` and places a group batch (see
`nmp.plan.build_group_batch`) on it: per-lane arrays are sharded along the
lane axis (`NamedSharding(P("lanes"))`), the episode seed schedule — the one
input with a folded seed axis — along both (`P("lanes", "seeds")`), and
everything lane-independent is replicated.  The execute layer's jitted
program then runs SPMD across the mesh: per-(lane, seed) work never crosses
a device, the only collectives are the scalar "any lane invokes / profiles"
reductions that feed the engine's `lax.cond` gates, so sharded per-cell
metrics are bit-identical to the single-device run for EVERY mesh shape.

Mesh shape: by default the execute layer auto-factors the visible device
count into (lane, seed) dims that minimize padded-cell waste for the plan at
hand (`auto_mesh_shape`); `REPRO_SWEEP_MESH=LxS` forces a shape.  A shape of
`(n, 1)` is exactly the historical 1-D lane mesh.

Lane counts are padded up to a lane-dim-divisible size by repeating the
first lane, and group seed axes up to a seed-dim-divisible width by
repeating seed slot 0 (padding lanes/slots are real, legal simulations whose
outputs the execute layer never reads).

Degrades gracefully: with a single device (plain CPU CI) `build_mesh`
returns None and the execute layer skips placement entirely.  Multi-device
CPU testing is forced with `XLA_FLAGS=--xla_force_host_platform_device_count=N`
(set before importing jax).

Multi-host scaffolding: when `REPRO_DIST_COORD` is set the process joins a
`jax.distributed` process group before any device query, the mesh spans
every host's devices (lane axis across hosts), batches are materialized as
global arrays via `jax.make_array_from_callback`, and `host_fetch` gathers
results back to every host (`multihost_utils.process_allgather`).  Without
the env knobs everything below is plain single-host jax.

Env knobs:

  REPRO_SWEEP_DEVICES   how many devices the sweep mesh uses: an integer,
                        or "all" (default).  Values outside 1..len(devices)
                        raise.
  REPRO_SWEEP_MESH      mesh shape as "LANESxSEEDS" (e.g. "2x2", "4x1"), or
                        "auto" (default).  The shape must factor the
                        selected device count exactly.
  REPRO_DIST_COORD      jax.distributed coordinator address (host:port);
                        unset = single-host (no process group is created).
  REPRO_DIST_NPROCS     number of processes in the group (with _COORD).
  REPRO_DIST_RANK       this process's rank in 0..NPROCS-1 (with _COORD).
"""
from __future__ import annotations

import os

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

LANE_AXIS = "lanes"
SEED_AXIS = "seeds"
_ENV_DEVICES = "REPRO_SWEEP_DEVICES"
_ENV_MESH = "REPRO_SWEEP_MESH"
_ENV_COORD = "REPRO_DIST_COORD"
_ENV_NPROCS = "REPRO_DIST_NPROCS"
_ENV_RANK = "REPRO_DIST_RANK"

_dist_initialized = False


# ---------------------------------------------------------------------------
# Multi-host scaffolding
# ---------------------------------------------------------------------------

def maybe_init_distributed() -> bool:
    """Join the `jax.distributed` process group named by REPRO_DIST_COORD /
    REPRO_DIST_NPROCS / REPRO_DIST_RANK.  A no-op (returns False) when
    REPRO_DIST_COORD is unset — the single-host degradation — and idempotent
    once initialized.  Must run before the first device query, which is why
    `sweep_devices` calls it."""
    global _dist_initialized
    if _dist_initialized:
        return True
    coord = os.environ.get(_ENV_COORD, "").strip()
    if not coord:
        return False
    try:
        nprocs = int(os.environ[_ENV_NPROCS])
        rank = int(os.environ[_ENV_RANK])
    except KeyError as e:
        raise ValueError(
            f"{_ENV_COORD}={coord!r} is set but {e.args[0]} is not; "
            f"multi-host runs need {_ENV_NPROCS} and {_ENV_RANK}") from None
    except ValueError:
        raise ValueError(
            f"{_ENV_NPROCS}/{_ENV_RANK} must be integers (got "
            f"{os.environ.get(_ENV_NPROCS)!r}/{os.environ.get(_ENV_RANK)!r})"
        ) from None
    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=nprocs, process_id=rank)
    _dist_initialized = True
    return True


def host_fetch(tree):
    """Bring a (possibly multi-host-sharded) pytree back to host numpy.

    Single-host (the common case): a plain `np.asarray` per leaf.  In a
    `jax.distributed` run the leaves are global arrays with non-addressable
    shards, so they are gathered across processes first — every host gets
    the full result, keeping the unfold/write-back logic host-agnostic.

    Note: the tests exercise the distributed path on the CPU backend only
    up to process-group init and global device visibility; no
    cross-process computation has been executed through it on any
    backend."""
    if tree is None:
        return None
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        tree = multihost_utils.process_allgather(tree, tiled=True)
    return jax.tree.map(np.asarray, tree)


# ---------------------------------------------------------------------------
# Device selection + mesh construction
# ---------------------------------------------------------------------------

def sweep_devices() -> list:
    """Devices the sweep mesh spans, honoring REPRO_SWEEP_DEVICES."""
    maybe_init_distributed()
    devices = jax.devices()
    raw = os.environ.get(_ENV_DEVICES, "all").strip().lower()
    if raw in ("", "all"):
        return devices
    try:
        n = int(raw)
    except ValueError:
        raise ValueError(
            f"{_ENV_DEVICES}={raw!r}: expected an integer or 'all'") from None
    if not 1 <= n <= len(devices):
        raise ValueError(f"{_ENV_DEVICES}={n} outside 1..{len(devices)} "
                         f"({len(devices)} {devices[0].platform} devices "
                         "visible)")
    return devices[:n]


def sweep_mesh_shape(n_devices: int) -> tuple[int, int] | None:
    """The (lane, seed) mesh shape forced by REPRO_SWEEP_MESH, or None when
    unset/"auto" (the execute layer then auto-factors per plan).

    The shape must factor `n_devices` exactly; anything else raises a
    ValueError naming the knob, the value and the available devices instead
    of surfacing an opaque mesh-construction error."""
    raw = os.environ.get(_ENV_MESH, "").strip().lower()
    if raw in ("", "auto"):
        return None
    parts = raw.split("x")
    try:
        if len(parts) != 2:
            raise ValueError
        dl, ds = int(parts[0]), int(parts[1])
        if dl < 1 or ds < 1:
            raise ValueError
    except ValueError:
        raise ValueError(
            f"{_ENV_MESH}={raw!r}: expected 'LANESxSEEDS' with two positive "
            "integers (e.g. '4x1', '2x2') or 'auto'") from None
    if dl * ds != n_devices:
        raise ValueError(
            f"{_ENV_MESH}={raw!r}: a {dl}x{ds} (lane x seed) mesh needs "
            f"{dl * ds} devices but {n_devices} device(s) are selected "
            f"(REPRO_SWEEP_DEVICES; {len(jax.devices())} visible) — the "
            "shape must factor the device count exactly")
    return dl, ds


def auto_mesh_shape(n_devices: int,
                    groups: list[tuple[int, int, int]]) -> tuple[int, int]:
    """Factor `n_devices` into the (lane, seed) dims that minimize total
    padded-cell work for a plan's groups.

    `groups` holds (n_lanes, n_seeds, weight) per group — weight is the
    per-cell cost proxy (episode count; every group shares the plan's op
    envelope).  Cost of a shape is Σ weight · pad(L, dl) · pad(S, ds); ties
    break toward the larger lane dim, so all-S=1 plans keep the historical
    1-D lane mesh exactly."""
    if n_devices <= 1:
        return (max(n_devices, 1), 1)

    def pad(n, d):
        return ((max(n, 1) + d - 1) // d) * d

    best = None
    for ds in range(1, n_devices + 1):
        if n_devices % ds:
            continue
        dl = n_devices // ds
        cost = sum(w * pad(L, dl) * pad(S, ds) for L, S, w in groups)
        key = (cost, ds)                 # ties -> smaller seed dim
        if best is None or key < best[0]:
            best = (key, (dl, ds))
    return best[1]


def build_mesh(devices=None, shape: tuple[int, int] | None = None
               ) -> Mesh | None:
    """2-D (lane, seed) mesh over `devices` (default: `sweep_devices()`).

    `shape` is (lane_dim, seed_dim); by default the REPRO_SWEEP_MESH
    override or, unset, the 1-D lane layout `(n, 1)` — callers with a plan
    in hand (sweep.run_grid) pass `auto_mesh_shape(...)` instead.  Returns
    None on a single device — the degraded path runs exactly the
    single-device program with no placement or padding."""
    devices = sweep_devices() if devices is None else list(devices)
    n = len(devices)
    if n <= 1:
        return None
    if shape is None:
        shape = sweep_mesh_shape(n) or (n, 1)
    dl, ds = int(shape[0]), int(shape[1])
    if dl * ds != n:
        raise ValueError(
            f"mesh shape {dl}x{ds} does not factor the {n} selected "
            f"device(s) ({len(jax.devices())} visible; see {_ENV_MESH})")
    return Mesh(np.asarray(devices).reshape(dl, ds), (LANE_AXIS, SEED_AXIS))


def mesh_desc(mesh: Mesh | None) -> dict:
    """JSON-friendly mesh description (benchmark records, memo keys)."""
    if mesh is None:
        return {"n_devices": 1, "shape": [1, 1],
                "axis_names": [LANE_AXIS, SEED_AXIS], "n_hosts": 1}
    return {"n_devices": int(mesh.devices.size),
            "shape": [int(s) for s in mesh.devices.shape],
            "axis_names": list(mesh.axis_names),
            "n_hosts": int(jax.process_count())}


def mesh_lane_dim(mesh: Mesh | None) -> int:
    return 1 if mesh is None else int(mesh.shape[LANE_AXIS])


def mesh_seed_dim(mesh: Mesh | None) -> int:
    return 1 if mesh is None else int(mesh.shape[SEED_AXIS])


def mesh_signature() -> str:
    """Stable signature of the mesh the next sweep would run on — part of
    grid memo keys so cached results never cross a mesh change (device
    count, forced shape, or host count)."""
    devices = sweep_devices()
    shape = os.environ.get(_ENV_MESH, "auto").strip().lower() or "auto"
    return (f"{devices[0].platform}:{len(devices)}:{shape}"
            f":{jax.process_count()}")


# ---------------------------------------------------------------------------
# Padding + placement
# ---------------------------------------------------------------------------

def padded_lane_count(n_lanes: int, mesh: Mesh | None) -> int:
    """Smallest lane count >= n_lanes divisible by the mesh's lane dim."""
    dl = mesh_lane_dim(mesh)
    return ((n_lanes + dl - 1) // dl) * dl


def padded_seed_count(n_seeds: int, mesh: Mesh | None) -> int:
    """Smallest seed width >= n_seeds divisible by the mesh's seed dim."""
    ds = mesh_seed_dim(mesh)
    return ((n_seeds + ds - 1) // ds) * ds


def pad_group_batch(batch: dict[str, np.ndarray],
                    n_to: int) -> dict[str, np.ndarray]:
    """Pad every lane-axis array to `n_to` lanes by repeating lane 0.

    Padding lanes are real, legal simulations (copies of lane 0) so the
    SPMD program needs no masking; the execute layer simply never reads
    their outputs."""
    if not batch:
        raise ValueError(
            "pad_group_batch: empty group batch (no arrays) — a group must "
            "hold at least one lane before it can be padded")
    n = next(iter(batch.values())).shape[0]
    if n_to == n:
        return batch
    assert n_to > n
    return {k: np.concatenate([v, np.repeat(v[:1], n_to - n, axis=0)])
            for k, v in batch.items()}


def pad_seed_axis(batch: dict[str, np.ndarray],
                  s_to: int) -> dict[str, np.ndarray]:
    """Pad the episode seed schedule's (L, S, E) seed axis to `s_to` slots
    by repeating slot 0 (padding slots re-simulate the lane's first seed;
    their outputs are dropped).  Only `ep_seed` carries a seed axis."""
    eps = batch["ep_seed"]
    if eps.shape[1] == s_to:
        return batch
    assert s_to > eps.shape[1]
    out = dict(batch)
    out["ep_seed"] = np.concatenate(
        [eps, np.repeat(eps[:, :1], s_to - eps.shape[1], axis=1)], axis=1)
    return out


def _put(arr, sharding):
    """Place one host array on the mesh; in a multi-host run the same host
    copy exists on every process, so each process contributes its
    addressable shards via `make_array_from_callback`."""
    if jax.process_count() > 1:
        arr = np.asarray(arr)
        return jax.make_array_from_callback(arr.shape, sharding,
                                            lambda idx: arr[idx])
    return jax.device_put(arr, sharding)


def shard_group_batch(batch: dict[str, np.ndarray], mesh: Mesh | None) -> dict:
    """Place a (padded) group batch: lane axis sharded, the episode seed
    schedule sharded over (lanes, seeds), trailing axes replicated.
    Without a mesh this is a plain host->device transfer."""
    import jax.numpy as jnp
    if mesh is None:
        return {k: jnp.asarray(v) for k, v in batch.items()}
    lane_sh = NamedSharding(mesh, P(LANE_AXIS))
    cell_sh = NamedSharding(mesh, P(LANE_AXIS, SEED_AXIS))
    return {k: _put(v, cell_sh if k == "ep_seed" else lane_sh)
            for k, v in batch.items()}


def shard_agent_batch(agent, mesh: Mesh | None):
    """Place a flat lane-major (L*S, ...) agent cell batch: the flattened
    cell axis shards over both mesh axes (lane-major order matches the
    (L, S) layout of the env grid, so no resharding inside the program)."""
    if mesh is None or agent is None:
        return agent
    sh = NamedSharding(mesh, P((LANE_AXIS, SEED_AXIS)))
    return jax.tree.map(lambda a: _put(a, sh), agent)


def replicate(x, mesh: Mesh | None):
    """Replicate a lane-independent array (e.g. TOM candidate tables)."""
    if mesh is None:
        return x
    return jax.device_put(x, NamedSharding(mesh, P()))
