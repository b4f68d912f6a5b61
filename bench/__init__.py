"""The benchmark of the BiSwift edge serving window (``bench/run.py``)."""
