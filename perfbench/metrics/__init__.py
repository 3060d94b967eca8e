"""One reader per metric of ``BENCHMARK.json``, found by the metric's name:
``<name>.py`` here defines ``read(run) -> float | None``."""
