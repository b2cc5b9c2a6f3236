"""Chip benchmark for the training and serving stack in ``src/repro``.

Run one cell of ``BENCHMARK.json`` per process on the machine that holds
the chips::

    python3 chipbench/run.py --workload gpt-a-2l.train --seed 7 --seconds 10 --trace 0

Everything that decides a number lives here, apart from the program:
traffic generation (``traffic.py`` over ``traffic/*.json``), weights from
the seed (``weights.py``), model FLOPs (``flops.py``), the peaks table
(``peaks.py``), the trace reduction (``reduce.py``), the plain float32
reference (``reference/``), the comparison that decides ``correct``
(``compare.py`` with ``limits/*.json``) and one reader per per-layer
metric (``metrics/*.py``).
"""
