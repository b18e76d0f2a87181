"""On-chip benchmark of the Euler solver: one cell per run, driven by data.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` at the repository root names the cells.  Each cell's
configuration (``configs/<config>.json``), traffic mix
(``traffic/<mix>.json``, whose loop kind is ``traffic/<loop>.py``) and
per-layer metric readers (``metrics/<metric>.py``) are files of their
own, found by name: a new cell, mix or metric is new files plus new
``BENCHMARK.json`` entries.  The yardstick lives here too: the graph
generator (``gen/``), the plain reference (``reference/``), the trace
reduction (``trace_reduce.py``) and the table of peaks (``peaks.json``).
Nothing here imports the system under test except :mod:`.harness`, which
drives ``repro.euler.EulerSolver``.
"""
