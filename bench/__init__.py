"""On-chip benchmark of the graph-query engine's served path.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once. Everything the yardstick depends on
lives here and imports nothing of ``src/`` except the system under test:
the deployment generators (``generators/``), the traffic generator
(``traffic.py``) with its key choosers (``keys/``) and arrival processes
(``arrivals/``), the query kinds with their plain numpy reference
(``queries/``, ``reference.py``), the controls of the check (``controls/``),
the trace reduction (``trace.py``) and the table of peaks (``peaks.py``).

Configurations, traffic mixes and per-layer metrics are files of their own
(``configs/<name>.json``, ``traffic/<name>.json``, ``metrics/<name>.py``),
found by the names in ``BENCHMARK.json``, and every module a file names by
its ``kind`` is found the same way (``registry.py``).
"""
