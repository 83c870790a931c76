"""Device ms of the `level_expand` kernel per answered count, in the
cells that report `count_s`."""
from readers import level_expand_ms as read  # noqa: F401
