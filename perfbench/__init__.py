"""The chip benchmark of this repository.

``python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the accelerator it
is started on and prints one JSON line. Everything that measures lives
here, apart from the program: traffic generation (``generator.py``), the
plain float32 references (``reference/``), the operation and byte counts
(``counts.py``), the table of peaks (``peaks.py``), the reduction of a
profiler trace to metrics (``trace.py``) and the comparison that decides
``correct`` (``oracle.py``). A configuration, a traffic mix, a cell's
limits and a per-layer metric are each a file of their own, found by the
name that ``BENCHMARK.json`` gives them (``spec.py``).
"""
