"""Per-layer metric readers, one file each: ``metrics/<metric>.py``.

A reader exposes ``read(ctx) -> float | None`` over a
``chipbench.harness.Context`` and holds its own rule for matching trace
events. It returns ``None`` when it finds nothing to read; the harness then
leaves the metric out of the result line. Device metrics of a cell on
several chips are the busiest device's value: that device sets the pace.
"""
