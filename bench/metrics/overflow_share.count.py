"""Share of dispatches bisected or escalated for overflow, in the cells
that report `count_s`."""
from readers import overflow_share as read  # noqa: F401
