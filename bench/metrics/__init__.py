"""Metric readers: ``<metric>.py`` holds ``read(run) -> float | None``."""
