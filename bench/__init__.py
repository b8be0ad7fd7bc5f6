"""On-chip benchmark of the tracked multi-camera detection path.

``python bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json``; see ``run.py``.
Everything the yardstick needs lives here: the traffic generator
(``traffic.py``), the plain reference (``reference.py``), the trace
reduction (``trace_reduce.py``), the FLOP count (``flops.py``), the
peaks (``peaks.json``), the layer name table (``layers.json``), one
file per configuration, traffic mix and per-layer metric.
"""
