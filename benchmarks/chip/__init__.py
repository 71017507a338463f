"""On-chip benchmark of the served logic path (see ``run.py``)."""
