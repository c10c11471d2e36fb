"""On-chip benchmark of the training step (see ``BENCHMARK.json`` and
``PERF.md`` at the repository root).  ``python bench/run.py --workload
<cell> --seed <n> --seconds <s> --trace <0|1>`` runs one cell once."""
