"""The benchmark of gtopkssgd_tpu: one cell, once, through ``Trainer`` on the chip.

``python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>``

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own under ``configs/``, ``traffic/`` and
``metrics/``, found by the name ``BENCHMARK.json`` gives it. From the
program the harness takes ``Trainer``/``TrainConfig``, the spans ``io``,
``dispatch`` and ``obs_read`` and the names of device operations; input,
reference, trace reduction, peaks and the comparison that decides
``correct`` live here.
"""
