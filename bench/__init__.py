"""Chip benchmark for HybridDNN serving: one cell (a model configuration under
one traffic mix) per run of ``bench/run.py``. See ``PERF.md`` at the root."""
