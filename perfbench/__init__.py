"""The benchmark: whole DiLoCo rounds through the CLI on the chip.

``BENCHMARK.json`` at the root names the cells; everything that belongs to
one configuration, one traffic mix or one per-layer metric is a data file
here, found by that name, a configuration's plain reference among them
(``reference/``). ``python perfbench/run.py --help``.
"""
