"""Device ms of the `level_expand` kernel per answered count, in the
4-clique cell."""
from readers import level_expand_ms as read  # noqa: F401
