"""The chip benchmark's library: everything a run of one cell needs.

`spec` reads ``BENCHMARK.json`` and finds each configuration, traffic mix,
limit file and metric reader by its name; `run.run_cell` drives one cell
through the served path and returns its result line.
"""
