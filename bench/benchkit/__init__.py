"""The on-chip benchmark's harness.

Everything here is general: a cell is found by name in ``BENCHMARK.json``,
and what belongs to one configuration, traffic mix, metric or cell lives in
its own data file under ``bench/`` (``configs/``, ``traffic/``,
``metrics/``, ``checks/``), which ``spec`` finds by that name.
"""
