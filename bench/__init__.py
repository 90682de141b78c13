"""On-chip benchmark of the LM serving engine (``python3 bench/run.py``)."""
