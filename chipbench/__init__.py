"""The on-chip benchmark of the shuffle service (see ``run.py``)."""
