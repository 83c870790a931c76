"""Kernel dispatches per answered count, in the 4-clique cell."""
from readers import dispatches_per_count as read  # noqa: F401
