"""The De-VertiFL benchmark: cells, traffic, metric readers and the
plain reference that decides ``correct``.  See ``bench/README.md``."""
