"""Metric readers, one file per metric, each with read(rec) -> number or None."""
