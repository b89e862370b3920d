"""The chip benchmark's harness: cells, traffic, weights, the reference that
decides ``correct``, the reduction from profiler traces to metrics, and the
operation and byte counts behind roofline and MFU shares.

Nothing here is imported by the program under test (``src/repro``); the
harness reaches the program only through the entry points listed in
``system.py``.
"""
