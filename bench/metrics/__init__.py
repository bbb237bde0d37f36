"""Per-layer metric readers, one module per metric of ``BENCHMARK.json``.

Each module ``<name>.py`` holds ``read(reading) -> float | None`` over a
``harness.Reading`` of the traced run.  A reader that finds nothing to
read returns None (never 0 for a share of a roofline or peak): the run
leaves the metric out of its line and counts it in ``per_layer_unread``,
which turns ``correct`` false.
"""
