"""The chip benchmark: cells, traffic, metric readers and the block
references that decide ``correct``.  ``python chipbench/run.py --help``
runs one cell."""
