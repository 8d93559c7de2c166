"""The chip benchmark of the Spec-QP query service (see ``bench/run.py``)."""
