"""Per-layer metric readers, one module per metric of ``BENCHMARK.json``.

Each module defines ``read(outcome, peaks) -> float | None``: ``outcome`` is
the run's ``bench.harness.Outcome`` (its request records, and in a traced
run ``outcome.trace``, the reduced trace of ``bench.trace``), ``peaks`` the
chip's row of ``bench/peaks.json``.  A reader that finds nothing to read
returns None, and the harness leaves the metric out of the result.
"""
