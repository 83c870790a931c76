"""Seconds per exact count of a counting job that enumerates (the
triangle cells)."""
from readers import seconds_per_count as read  # noqa: F401
