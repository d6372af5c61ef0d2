"""The chip benchmark of this repository (see ``run.py``)."""
