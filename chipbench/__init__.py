"""The chip benchmark of the GraphGen+ training path (see ``run.py``)."""
