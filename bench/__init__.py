"""Chip benchmark of the out-of-core Cholesky solver (see ``run.py``)."""
