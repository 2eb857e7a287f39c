"""The chip benchmark of SODDA fits; see ``bench/run.py``."""
