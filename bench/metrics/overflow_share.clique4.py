"""Share of dispatches bisected or escalated for overflow, in the
4-clique cell."""
from readers import overflow_share as read  # noqa: F401
