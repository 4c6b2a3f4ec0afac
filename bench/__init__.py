"""The benchmark: one cell per run of ``bench/run.py`` (see BENCHMARK.json)."""
