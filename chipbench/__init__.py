"""The chip benchmark of HWA training: see ``run.py`` and ``PERF.md``."""
