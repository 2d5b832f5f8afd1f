"""The chip benchmark: one command (``bench/run.py``) driven by data files.

Configurations (``configs/``), traffic mixes (``traffic/``), per-cell
correctness limits (``cells/``), per-metric readers (``metrics/``) and the
plain references (``references/``) are found by the names that
``BENCHMARK.json`` gives them; see ``harness.py``.
"""
