"""The benchmark's only door into the program under test.

Everything the harness hands the program goes through here: the harness's
own traces become the program's `Trace` objects, its protocols become
`Scenario`s, and a configuration file's sizes become an `NMPConfig`.  The
program's entry point is `run_grid`."""
from __future__ import annotations

import sys
from pathlib import Path

SRC = Path(__file__).resolve().parents[1] / "src"


def _import_program():
    if not (SRC / "repro").is_dir():
        raise SystemExit(f"chipbench: the program is not at {SRC}")
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


def nmp_config(fields: dict):
    _import_program()
    from repro.nmp.config import NMPConfig
    return NMPConfig(**fields)


def to_trace(tr):
    """The program's `Trace` holding the harness trace's arrays."""
    _import_program()
    from repro.nmp.traces import Trace
    return Trace(tr.name, tr.dest, tr.src1, tr.src2, tr.n_pages,
                 tr.read_write, tr.program_id, tr.iter_ops)


def scenario(p, name: str, program_trace):
    """The program's `Scenario` for a harness `Protocol`."""
    _import_program()
    from repro.nmp.scenarios import Scenario
    return Scenario(name=name, trace=program_trace, technique=p.technique,
                    mapper=p.mapper, seed=p.seed, episodes=p.episodes)


def run_grid(scenarios, cfg):
    _import_program()
    from repro.nmp.sweep import run_grid as program_run_grid
    return program_run_grid(scenarios, cfg)

