"""The chip benchmark: cells, traffic, metric readers and the reference that
decides ``correct``.  ``python chipbench/run.py --help`` runs one cell."""
