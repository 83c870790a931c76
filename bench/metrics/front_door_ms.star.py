"""Ms per answered request spent outside the engine's execution, in
the cells that report `star_count_s`."""
from span_readers import front_door_ms as read  # noqa: F401
