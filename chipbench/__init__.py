"""The chip benchmark of this repository: `python3 chipbench/run.py`.

See `chipbench/harness.py` for how a cell is found and run, and
`BENCHMARK.json` at the root for the cells and metrics."""
