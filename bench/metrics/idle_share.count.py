"""Idle share of the device in the traced window, in the cells that
report `count_s`."""
from readers import idle_share as read  # noqa: F401
