"""The benchmark harness: one general driver for every cell that
``BENCHMARK.json`` names.  Configurations (``configs/``), traffic mixes
(``mixes/``) and metric readers (``metrics/``) are found by name, so a
new cell, mix, configuration or metric is new files plus an entry in
``BENCHMARK.json``."""
