"""CPU tests of the harness's arithmetic: the trace reduction, the
per-layer readers, the rate over whole calls, the comparison's numbers,
the reference's routing, the accelerator check and the benchmark's
files."""
from __future__ import annotations

import importlib
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

import numpy as np

from chipbench import compare, grid, reference, run, tracing, traffic
from chipbench.tracing import Event, Trace

ROOT = Path(__file__).resolve().parents[1]


def _trace():
    # One device: a program (module) from 1.0 to 5.0 s whose loop op nests
    # two leaf ops, then a second program 6.0-7.0 s; harness spans around
    # two calls.
    ops = [Event("%while.1 = (...) while(...)", 1.0, 5.0),
           Event("%fusion.2 = f32[8] fusion(...)", 1.5, 3.0),
           Event("%fusion.3 = f32[8] fusion(...)", 3.0, 4.0),
           Event("%copy.4 = f32[8] copy(...)", 6.0, 7.0)]
    modules = [Event("jit__run_sweep(123)", 1.0, 5.0),
               Event("jit_iota(9)", 6.0, 7.0)]
    spans = [Event("run_grid", 0.5, 5.5), Event("run_grid", 5.5, 8.0),
             Event("collect", 7.0, 7.5)]
    return Trace({"/device:TPU:0": ops}, {"/device:TPU:0": modules}, spans)


def test_reduce_busy_window_program_and_breakdown():
    red = tracing.reduce(_trace())
    assert red["window_s"] == pytest.approx(7.5)
    assert red["busy_s"] == pytest.approx(5.0)          # [1, 5] and [6, 7]
    assert red["program_s"] == pytest.approx(4.0)       # only _run_sweep
    ops = dict(red["breakdown"]["device_ops"])
    assert set(ops) == {"fusion.2", "fusion.3", "copy.4"}   # leaves only
    assert ops["fusion.2"] == pytest.approx(1.5)
    # idle [0.5, 1], [5, 6] and [7, 8], longest first; `collect` covers
    # only half of the last gap, which the second call's span covers whole
    gaps = red["breakdown"]["idle_gaps"]
    assert [g[0] for g in gaps] == ["run_grid"] * 3
    assert [g[1] for g in gaps] == pytest.approx([1.0, 1.0, 0.5])


def test_reduce_nothing_to_read():
    assert tracing.reduce(Trace({}, {}, [Event("run_grid", 0, 1)])) is None
    assert tracing.reduce(Trace({"d": [Event("x", 0, 1)]}, {}, [])) is None


@pytest.mark.parametrize("events,want", [
    ([(0, 1), (0.5, 2), (3, 4)], [(0, 2), (3, 4)]),
    ([(0, 4), (1, 2), (2, 3)], [(0, 4)]),
    ([], []),
])
def test_union(events, want):
    assert tracing.union([Event("e", a, b) for a, b in events]) == want


def test_gaps_and_label():
    assert tracing.gaps([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]
    spans = [Event("outer", 0, 10), Event("inner", 2, 4)]
    assert tracing.label((2, 4), spans) == "inner"
    assert tracing.label((5, 6), spans) == "outer"
    assert tracing.label((11, 12), spans) == "outside spans"


def test_readers():
    rec = {"trace": {"window_s": 10.0, "busy_s": 8.0, "program_s": 6.0},
           "traced_ops": 3e6}
    read = lambda n: importlib.import_module(f"chipbench.metrics.{n}").read
    assert read("device_idle_share")(rec) == pytest.approx(20.0)
    assert read("sweep_device_ns_per_op")(rec) == pytest.approx(2000.0)
    for name in ("device_idle_share", "sweep_device_ns_per_op"):
        assert read(name)({"trace": None}) is None


class _Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_grid_rate_spans_whole_calls(monkeypatch):
    clock = _Clock()
    monkeypatch.setattr(grid.time, "perf_counter", clock)
    cell = object.__new__(grid.Cell)
    cell.calls = []
    proto = SimpleNamespace(episodes=2)
    import numpy as np

    def fake_call(call):
        clock.t += 3.0                       # every call takes 3 s
        return [proto], {"ops": np.array([[10.0, 10.0, 99.0]])}
    cell._call = fake_call
    rec = cell.window(7.0)
    # the third call is the first to end after 7 s: 3 calls, 9 s, and only
    # the two real episodes of each call count
    assert rec["calls"] == 3
    assert rec["window_s"] == pytest.approx(9.0)
    assert rec["e2e"]["sim_ops_per_s"] == pytest.approx(3 * 20.0 / 9.0)


def _answer(n_epochs=4):
    out = {k: np.array([7.0], np.float32) for k in
           ("cycles", "ops", "epochs", "hops_sum", "util_sum",
            "access_total", "migrations", "pages_migrated",
            "access_on_migrated")}
    out["energy"] = np.ones((1, 9), np.float32)
    out["opc_t"] = np.full((1, n_epochs), 0.5, np.float32)
    out["valid_t"] = np.full((1, n_epochs), 128, np.uint16)
    out["invoke_t"] = np.ones((1, n_epochs), np.uint16)
    return out


def test_compare_counts_and_gaps():
    want = _answer()
    assert compare.compare(_answer(), want, 1) == (0, 0.0, 30)
    got = _answer()
    got["valid_t"][0, 2] = 127               # a count: exact
    got["cycles"] = np.array([7.0007], np.float32)
    bad, gap, _ = compare.compare(got, want, 1)
    assert bad == 1 and gap == pytest.approx(1e-4, rel=1e-3)
    got = _answer()
    del got["opc_t"]                         # missing: wholly wrong
    got["ops"] = np.array([7.0, 7.0], np.float32)
    bad, gap, _ = compare.compare(got, want, 1)
    assert bad == 0 and gap == compare.WRONG
    got = _answer()
    got["energy"][0, 3] = np.nan
    assert compare.compare(got, want, 1)[1] == compare.WRONG
    assert not compare.complete(got, 7, 1)
    assert compare.complete(_answer(), 7, 1)
    assert not compare.complete(_answer(), 8, 1)


@pytest.mark.parametrize("src,dst,want", [
    ([0], [15], {0: 4, 1: 4, 2: 4, 21: 4, 22: 4, 23: 4}),   # X then Y
    ([15], [0], {9: 4, 10: 4, 11: 4, 12: 4, 13: 4, 14: 4}),
    ([5, 5], [5, 6], {4: 4}),                                # zero hops
])
def test_reference_xy_routes(src, dst, want):
    mesh = reference.Mesh({"mesh_x": 4, "mesh_y": 4})
    loads = mesh.link_loads(np.array(src), np.array(dst), 4.0)
    assert mesh.n_links == 24 and loads.shape == (24,)
    assert {i: v for i, v in enumerate(loads) if v} == want
    assert loads.sum() == 4.0 * mesh.hops(np.array(src), np.array(dst)).sum()


def test_traffic_same_sizes_for_every_seed():
    mix = {"apps": ["KM", "RBM"], "techniques": ["bnmp", "pei"],
           "mappers": ["none", "tom"], "seeds_per_cell": 1, "n_ops": 1024,
           "episodes": 1, "trace_sets": 3}
    shapes = []
    for seed in (1, 2**31 + 7):
        sets = traffic.trace_sets(mix, seed)
        assert len(sets) == 3
        assert not np.array_equal(sets[0]["KM"].dest, sets[1]["KM"].dest)
        calls = [traffic.grid_call(mix, sets, seed, c) for c in range(4)]
        assert calls[3][0].trace is sets[0]["KM"]
        shapes.append([(p.trace.name, p.trace.n_ops, p.trace.n_pages,
                        p.technique, p.mapper) for p in calls[1]])
    assert shapes[0] == shapes[1] and len(shapes[0]) == 8
    picks = traffic.pick_calls(9, 5, 8)
    assert picks == traffic.pick_calls(9, 5, 8) and max(picks) < 5


@pytest.mark.parametrize("devices,chips", [
    ([SimpleNamespace(platform="cpu")], 1),
    ([], 1),
    ([SimpleNamespace(platform="tpu")], 4),
])
def test_refuses_without_enough_tpu_chips(devices, chips):
    with pytest.raises(SystemExit):
        run.require_devices(devices, chips)


@pytest.mark.parametrize("seconds,trace,want", [
    (51.0, False, 51.0), (51.0, True, run.TRACE_SECONDS), (3.0, True, 3.0)])
def test_traced_window_is_cut(seconds, trace, want):
    assert run.window_seconds(seconds, trace) == want


def test_accepts_tpu():
    devs = [SimpleNamespace(platform="tpu")] * 4
    assert run.require_devices(devs, 4) is devs


def test_benchmark_files_are_found_by_name():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for cell in bench["workloads"]:
        _, c, cfg_file, mix = run.load_cell(cell["name"])
        assert mix["kind"] == "grid"
        assert set(mix["limits"]) == {"mismatched_counts", "max_rel_gap"}
        assert set(cfg_file["nmp_config"]) and cfg_file["reduced"] == next(
            x["reduced"] for x in bench["configs"] if x["name"] == c["config"])
        for m in run.metrics_for(bench, c["name"], "per_layer"):
            reader = importlib.import_module(f"chipbench.metrics.{m['name']}")
            assert callable(reader.read)
    names = [m["name"] for m in bench["end_to_end"]]
    assert "setup_s" in names
