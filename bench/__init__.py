"""On-chip benchmark of back-to-back MV refresh rounds (see ``bench/run.py``)."""
