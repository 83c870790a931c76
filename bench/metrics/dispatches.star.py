"""Kernel dispatches per answered count, in the cells that report
`star_count_s`."""
from readers import dispatches_per_count as read  # noqa: F401
