"""Share of the streamed candidate-window slots that held a real
neighbour, in the cells that report `count_s`."""
from span_readers import window_fill as read  # noqa: F401
