"""The tree-training benchmark (see ``bench/run.py`` and ``BENCHMARK.json``)."""
