"""Device-idle ms per dispatch in the outer loop's host turn, in the
cells that report `star_count_s`."""
from span_readers import host_turn_ms as read  # noqa: F401
