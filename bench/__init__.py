"""The benchmark: one command (``run.py``), the cells' configurations
(``configs/``), traffic mixes (``traffic/``), the drivers that generate
them and compare what they produced (``drivers/``), per-layer metric
readers (``metrics/``), the seeded data generator, the trace reduction
and the reference the runs are compared with."""
