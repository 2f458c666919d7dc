"""The chip benchmark of the population search: ``python bench/run.py``."""
