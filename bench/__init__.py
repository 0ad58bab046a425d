"""Chip benchmark of the served butterfly path (see ``bench/run.py``)."""
