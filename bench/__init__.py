"""On-chip benchmark of the Hyena LM: one cell per run (see ``run.py``)."""
