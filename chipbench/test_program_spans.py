"""The program's host spans as `program_spans` reads them: its arithmetic on
a hand-built trace, a small two-app `run_grid` under `jax.profiler` on the
CPU, and its command line at a small size."""
from __future__ import annotations

import json
import sys

import jax
import numpy as np
import pytest

from chipbench import program, program_spans, run, tracing
from chipbench.conftest import CONFIG, SMALL_MIX
from chipbench.program_spans import Span
from chipbench.tracing import Event, Trace

program._import_program()
from repro.nmp import Scenario, make_trace, partition, run_grid  # noqa: E402

PHASES = {"plan", "build", "place", "dispatch", "wait", "fetch", "unfold",
          "stack"}
LANDING = {"wait", "fetch", "unfold"}


def _trace():
    # Two harness calls [0, 10] and [10, 20]; the device busy [3, 6] and
    # [13, 16].  Each call's program spans: plan, build, place, dispatch
    # and stack under the root on the main thread; wait, fetch and unfold
    # on the landing thread, with a second fetch nested in the unfold.
    ops = [Event("%fusion.1 = f32[8] fusion(...)", 3.0, 6.0),
           Event("%fusion.1 = f32[8] fusion(...)", 13.0, 16.0)]
    modules = [Event("jit__run_sweep(1)", 3.0, 6.0),
               Event("jit__run_sweep(1)", 13.0, 16.0)]
    harness = [Event("run_grid", 0.0, 10.0), Event("run_grid", 10.0, 20.0)]
    main, land = "/host:CPU/0", "/host:CPU/1"
    spans = []
    for c, t in ((1, 0.0), (2, 10.0)):
        ids = {"call": c, "group": 0}
        for name, a, b, thread, stats in [
                ("run_grid", 0.5, 9.5, main, {"call": c, "lanes": 54}),
                ("plan", 0.5, 1.0, main, {"call": c}),
                ("build", 1.0, 2.0, main, dict(ids, lanes=54)),
                ("place", 2.0, 2.5, main, dict(ids, h2d_bytes=1000)),
                ("dispatch", 2.5, 3.0, main, ids),
                ("wait", 3.0, 6.0, land, ids),
                ("fetch", 6.0, 7.0, land, dict(ids, d2h_bytes=10)),
                ("unfold", 7.0, 8.5, land, dict(ids, lanes=54)),
                ("fetch", 7.5, 8.0, land, dict(ids, d2h_bytes=5)),
                ("stack", 9.0, 9.5, main, {"call": c})]:
            spans.append(Span(name, a + t, b + t, thread, stats))
    return (Trace({"/device:TPU:0": ops}, {"/device:TPU:0": modules},
                  harness), sorted(spans, key=lambda s: s.start))


def test_self_times_nest_on_one_thread():
    spans = [Span("root", 0, 10, "t", {}), Span("a", 1, 3, "t", {}),
             Span("b", 3, 6, "t", {}), Span("c", 4, 5, "t", {}),
             Span("d", 7, 7.5, "t", {})]
    assert program_spans.self_times(spans) == pytest.approx(
        [10 - 2 - 3 - 0.5, 2, 3 - 1, 1, 0.5])


@pytest.mark.parametrize("a,b,want", [
    ([(0, 3), (6, 13)], [(0.5, 8.5), (9, 9.5)], 2.5 + 2.5 + 0.5),
    ([(0, 1)], [(1, 2)], 0.0),
    ([], [(0, 1)], 0.0),
])
def test_overlap(a, b, want):
    assert program_spans.overlap(a, b) == pytest.approx(want)


def test_reduce_self_counters_calls_and_idle():
    prog = program_spans.reduce(*_trace())
    assert prog["calls"] == 2
    assert prog["self_s"] == pytest.approx({
        "run_grid": 2 * 6.0, "plan": 1.0, "build": 2.0, "place": 1.0,
        "dispatch": 1.0, "wait": 6.0, "fetch": 3.0, "unfold": 2.0,
        "stack": 1.0})
    assert prog["counters"] == {
        "run_grid.lanes": 108, "build.lanes": 108, "place.h2d_bytes": 2000,
        "fetch.d2h_bytes": 30, "unfold.lanes": 108}
    # idle [0, 3], [6, 13] and [16, 20]; the phases cover [0.5, 8.5] and
    # [9, 9.5] of each call, so 0.5 + 0.5 + 1 + 0.5 + 0.5 s go unattributed
    assert prog["idle_s"] == pytest.approx(14.0)
    assert prog["idle_unattributed_s"] == pytest.approx(3.0)
    assert prog["run_grid_s"] == pytest.approx(20.0)
    assert prog["phase_cover_s"] == pytest.approx(17.0)


def test_split_per_call():
    got = program_spans.split(program_spans.reduce(*_trace()))
    assert got == pytest.approx({
        "grid_plan_ms_per_call": 500.0,
        "grid_build_ms_per_call": 1000.0,
        "grid_transfer_ms_per_call": 2000.0,    # place 1 s + fetch 3 s
        "grid_land_ms_per_call": 1500.0,        # unfold 2 s + stack 1 s:
                                                # the wait is not landing
        "host_idle_unattributed_share": 100.0 * 3.0 / 14.0,
        "phase_cover_share": 100.0 * 17.0 / 20.0})


def test_nothing_to_read():
    trace, spans = _trace()
    assert program_spans.reduce(trace, []) is None          # no program spans
    assert program_spans.reduce(trace._replace(device_ops={}), spans) is None
    outside = [s._replace(start=s.start + 100, end=s.end + 100)
               for s in spans]
    assert program_spans.reduce(trace, outside) is None
    assert program_spans.split(None) == {}


def test_harness_numbers_read_no_program_span():
    # the harness's reduction reads its own spans alone
    trace, _ = _trace()
    red = tracing.reduce(trace)
    assert red["window_s"] == pytest.approx(20.0)
    assert red["busy_s"] == pytest.approx(6.0)
    assert 20.0 - red["busy_s"] == pytest.approx(
        program_spans.reduce(*_trace())["idle_s"])


def _scenarios():
    traces = [make_trace(app, n_ops=512, seed=3) for app in ("KM", "RBM")]
    return [Scenario(name=f"{tr.name}/{m}", trace=tr, technique="bnmp",
                     mapper=m) for tr in traces for m in ("none", "tom")]


@pytest.mark.parametrize("land", ["async", "sync"])
def test_run_grid_spans_under_the_profiler(land, monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_SWEEP_LAND", land)
    placed = []
    shard = partition.shard_group_batch

    def spy(batch, mesh):
        out = shard(batch, mesh)
        placed.append(sum(v.nbytes for v in out.values()))
        return out
    monkeypatch.setattr(partition, "shard_group_batch", spy)

    scs = _scenarios()
    off = run_grid(scs)
    placed.clear()
    with tracing.capture(str(tmp_path)):
        on = run_grid(scs)

    for k, v in off.metrics.items():
        np.testing.assert_array_equal(on.metrics[k], v, err_msg=k)
    for a, b in zip(jax.tree.leaves(on.final_env),
                    jax.tree.leaves(off.final_env)):
        np.testing.assert_array_equal(a, b)

    spans = program_spans.load(str(tmp_path))
    roots = [s for s in spans if s.name == "run_grid"]
    assert len(roots) == 1
    root = roots[0]
    call = root.stats["call"]
    assert root.stats["lanes"] == 4 and root.stats["groups"] == 1
    assert {s.name for s in spans} == PHASES | {"run_grid"}
    by_name = {s.name: s for s in spans}
    for s in spans:
        assert s.stats["call"] == call, s
        assert root.start <= s.start <= s.end <= root.end, s
        if s.name in LANDING:
            assert s.stats["group"] == 0
            assert (s.thread != root.thread) == (land == "async"), s
        else:
            assert s.thread == root.thread, s
    assert (by_name["build"].stats["lanes"],
            by_name["build"].stats["lanes_padded"],
            by_name["build"].stats["seeds_padded"]) == (4, 4, 1)
    assert by_name["place"].stats["h2d_bytes"] == placed[0] > 0
    assert by_name["fetch"].stats["d2h_bytes"] > 0
    assert by_name["unfold"].stats["lanes"] == 4
    # the phases follow one another in the order the call runs them
    order = [s.name for s in spans if s.name != "run_grid"]
    assert order[:4] == ["plan", "build", "place", "dispatch"]
    assert order[-1] == "stack"
    main = [s for s in spans if s.thread == root.thread]
    assert dict(zip([s.name for s in main],
                    program_spans.self_times(main)))["run_grid"] == (
        pytest.approx(root.end - root.start
                      - sum(s.end - s.start for s in main if s is not root)))


def test_command_line_at_a_small_size(monkeypatch, capsys):
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    cell = {"name": "paper_4x4.small_grid", "config": "paper_4x4",
            "traffic": "small_grid", "chips": 1}
    monkeypatch.setattr(run, "load_cell",
                        lambda name: (bench, cell, CONFIG, SMALL_MIX))
    monkeypatch.setattr(run, "configure_cache", lambda: None)
    monkeypatch.setattr(run, "require_devices", lambda devices, chips: devices)
    monkeypatch.setattr(sys, "argv", [
        "program_spans.py", "--workload", cell["name"],
        "--seed", str(2**33 + 5), "--seconds", "0.2"])
    assert program_spans.main() == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["cell"] == cell["name"] and line["calls"] >= 1
    assert line["sim_ops_per_s"] > 0
    # the CPU backend writes no device plane, so there is no window to split
    assert line["program"] is None and line["split"] == {}
