"""Idle share of the device in the traced window, in the cells that
report `star_count_s`."""
from readers import idle_share as read  # noqa: F401
