"""Chip benchmark of the semantic-query gateway (see ``run.py``)."""
