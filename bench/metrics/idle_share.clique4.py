"""Idle share of the device in the traced window, in the 4-clique
cell."""
from readers import idle_share as read  # noqa: F401
