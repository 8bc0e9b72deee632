"""The benchmark: ``BENCHMARK.json``'s command, harness, cells and yardsticks.

See ``PERF.md`` for what is measured and why, and ``benchmarks/run.py`` for
the one command.
"""
