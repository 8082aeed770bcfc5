"""The chip benchmark: one command (``run.py``) that serves a cell of
``BENCHMARK.json`` on the accelerator, reads its metrics and checks what it
served against a plain float32 reference. See ``run.py`` for the command
and ``PERF.md`` for the cells."""
