#!/usr/bin/env python3
"""Run one benchmark cell once, on the machine's accelerator.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of `workloads` in BENCHMARK.json) names a configuration,
whose sizes are `chipbench/configs/<config>.json`, and a traffic mix,
`chipbench/mixes/<traffic>.json`, whose `kind` picks the driver
(`chipbench/<kind>.py`, a `Cell`; today `grid.py`).  The run makes its
inputs from `--seed`, warms up every shape it will use, measures for
`--seconds`, checks what the timed path produced against the reference,
and prints one JSON line last on standard output.  With `--trace 0` the
metrics are the cell's end-to-end metrics; with `--trace 1` the window,
cut to at most `TRACE_SECONDS`, runs under the profiler and the metrics
are the cell's per-layer metrics, each read by
`chipbench/metrics/<name>.py`.

It refuses (non-zero exit, no result) to run anywhere but on a TPU with at
least as many chips as the cell asks for.  JAX's persistent compilation
cache lives in `chipbench/.jax_cache` inside the checkout."""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
CACHE_DIR = HERE / ".jax_cache"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
HIT_EVENT = "/jax/compilation_cache/cache_hits"
TRACE_SECONDS = 10.0     # the profiler's window: its trace is read in-run


def process_start() -> float:
    """When this process started (Linux), else when this module loaded."""
    try:
        stat = Path("/proc/self/stat").read_text()
        ticks = int(stat[stat.rindex(")") + 2:].split()[19])
        btime = next(int(line.split()[1]) for line in
                     Path("/proc/stat").read_text().splitlines()
                     if line.startswith("btime"))
        return min(T_START, btime + ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, StopIteration):
        return T_START


def load_cell(name: str) -> tuple[dict, dict, dict, dict]:
    """(benchmark, cell, configuration file, mix) for a cell name."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"chipbench: no cell {name!r} in BENCHMARK.json")
    cell = cells[name]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg_file = json.loads((ROOT / config["file"]).read_text())
    from chipbench import traffic
    return bench, cell, cfg_file, traffic.load_mix(cell["traffic"])


def metrics_for(bench: dict, cell: str, group: str) -> list[dict]:
    """The metrics of `group` (end_to_end / per_layer) this cell reports."""
    return [m for m in bench[group]
            if cell in m.get("workloads", [cell])]


def configure_cache() -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, whatever the machine sets (the program reads the same
    variable), keeping every program the cell compiles."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_devices(devices, chips: int):
    """The accelerator check: a TPU with at least `chips` devices, else
    SystemExit (and no result)."""
    if not devices or devices[0].platform != "tpu":
        platform = devices[0].platform if devices else "none"
        raise SystemExit(f"chipbench: no TPU (JAX's first device is "
                         f"{platform!r}); refusing to measure")
    if len(devices) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips, JAX "
                         f"sees {len(devices)}")
    return devices


def window_seconds(seconds: float, trace: bool) -> float:
    """The measured window: `--seconds`, or at most `TRACE_SECONDS` under
    the profiler, whose trace of a longer window takes minutes to write and
    reduce, past a run's time limit."""
    return min(seconds, TRACE_SECONDS) if trace else seconds


def say(*lines: str, err: bool = False) -> None:
    for line in lines:
        print(line, file=sys.stderr if err else sys.stdout, flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    t_proc = process_start()

    configure_cache()
    import jax

    bench, cell, cfg_file, mix = load_cell(args.workload)
    devices = require_devices(jax.devices(), cell["chips"])

    counts = {"compiles": 0, "hits": 0}
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, _s, **_kw: counts.__setitem__(
            "compiles", counts["compiles"] + (ev == COMPILE_EVENT)))
    jax.monitoring.register_event_listener(
        lambda ev, **_kw: counts.__setitem__(
            "hits", counts["hits"] + (ev == HIT_EVENT)))

    from chipbench import tracing
    driver = importlib.import_module(f"chipbench.{mix['kind']}").Cell(
        cfg_file["nmp_config"], mix, args.seed)
    driver.setup()
    setup_s = time.time() - t_proc
    compiles0 = counts["compiles"]

    trace = None
    if args.trace:
        logdir = tempfile.mkdtemp(prefix="chipbench-trace-")
        try:
            with tracing.capture(logdir):
                rec = driver.window(window_seconds(args.seconds, True))
            trace = tracing.reduce(tracing.load(logdir))
        finally:
            shutil.rmtree(logdir, ignore_errors=True)
    else:
        rec = driver.window(args.seconds)
    rec["trace"] = trace
    compiles_in_window = counts["compiles"] - compiles0
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)

    attempted, failed, checks, notes = driver.check()
    checks["compiles_in_window"] = (compiles_in_window, 0)
    correct = all(v <= lim for v, lim in checks.values())

    if args.trace:
        metrics = {}
        for m in metrics_for(bench, cell["name"], "per_layer"):
            reader = importlib.import_module(f"chipbench.metrics.{m['name']}")
            value = reader.read(rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(rec["e2e"], setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in metrics_for(bench, cell["name"], "end_to_end")}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    if trace is not None:
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])

    say(f"chipbench: cell={cell['name']} seed={args.seed} "
        f"device_kind={devices[0].device_kind!r} count={len(devices)} "
        f"cache_dir={CACHE_DIR} cache_hits={counts['hits']} "
        f"compiles_total={counts['compiles']} "
        f"compiles_in_window={compiles_in_window} "
        f"peak_bytes_in_use={peak} setup_s={setup_s}",
        "chipbench: window " + " ".join(
            f"{k}={v}" for k, v in rec.items()
            if k not in ("trace", "e2e")),
        "chipbench: check " + " ".join(f"{k}={v}" for k, v in notes.items()))
    if trace is not None:
        say("chipbench: trace " + json.dumps(
            {k: v for k, v in trace.items() if k != "breakdown"}))
    limits = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    say(*[f"check {k} {d['value']} limit {d['limit']}"
          for k, d in limits.items()], err=True)
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if trace is not None:
        result["breakdown"] = trace["breakdown"]
    result["limits"] = limits
    say(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
