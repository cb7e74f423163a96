"""Per-layer metric readers, one module per metric base name.

``fold_us.steady`` and ``fold_us.saturated`` are both read by
``fold_us.py``: the suffix names the end-to-end metric the reading moves,
and the reader is the same.  Each module defines ``read(run) -> float |
None``; ``None`` means the run held nothing to read, and the harness then
leaves the metric out of the result line.
"""
