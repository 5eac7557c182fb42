"""One reader per metric, named as the metric in ``BENCHMARK.json``:
``read(run) -> float | None``. ``run`` is ``bench.lib.harness.Run``. A
reader that finds nothing to read returns None and the metric is left out
of the result line."""
