"""Device-idle ms per dispatch in the outer loop's host turn, in the
4-clique cell."""
from span_readers import host_turn_ms as read  # noqa: F401
