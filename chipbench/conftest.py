"""Fixtures of the tests that drive a whole run on the CPU at a small size:
the accelerator check skipped, the persistent cache left alone, and the
cell's mix cut to two apps, 4,096-op traces and two trace sets, with the
cell's own limits."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import jax
import pytest

HERE = Path(__file__).resolve().parent
CELL_MIX = json.loads((HERE / "mixes" / "baseline_grid.json").read_text())
SMALL_MIX = dict(CELL_MIX, apps=["KM", "RBM"], techniques=["bnmp", "pei"],
                 n_ops=4096, trace_sets=2)
CONFIG = json.loads((HERE / "configs" / "paper_4x4.json").read_text())


@pytest.fixture
def small_run(monkeypatch, capsys):
    """`small_run()` runs the small grid cell of the paper 4x4
    configuration through `run.main` and returns its result line.
    Compiled programs are dropped before and after, so a fault planted in
    the program is traced into it."""
    from chipbench import run

    def go() -> dict:
        def small(name):
            bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
            c = {"name": name, "config": "paper_4x4",
                 "traffic": "small_grid", "chips": 1}
            return bench, c, CONFIG, SMALL_MIX
        monkeypatch.setattr(run, "load_cell", small)
        monkeypatch.setattr(run, "configure_cache", lambda: None)
        monkeypatch.setattr(run, "require_devices",
                            lambda devices, chips: devices)
        monkeypatch.setattr(sys, "argv", [
            "run.py", "--workload", "paper_4x4.small_grid",
            "--seed", str(2**33 + 5), "--seconds", "0.2", "--trace", "0"])
        jax.clear_caches()
        try:
            assert run.main() == 0
        finally:
            jax.clear_caches()
        return json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return go
