"""On-chip benchmark of the Top-K sparse eigensolver (see ``bench/run.py``)."""
