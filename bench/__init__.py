"""On-chip benchmark of the tracked multi-camera detection path.

``python bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json``; see ``run.py``.
Everything the yardstick needs lives here: the traffic generator
(``generator.py``), the shared reference and the tracker's
(``reference.py``), the comparison that decides ``correct``
(``compare.py``), the trace reduction (``trace_reduce.py``), the peaks
(``peaks.json``), the layer name table (``layers.json``), and one file
per configuration (with its limits), detector family (its weights,
reference and operation counts), traffic mix and per-layer metric.
"""
