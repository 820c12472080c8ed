"""The benchmark of the NMP simulator on the chip.

`run.py` runs one cell; `traffic.py` makes the inputs from the mixes in
`mixes/`; `grid.py` drives the program through `program.py`;
`reference.py` is the plain reference the answers are compared with
(`compare.py`), and `control.py` its lower-precision control; `tracing.py`
and `metrics/` turn a profiler trace into per-layer numbers."""
