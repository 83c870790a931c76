"""Share of the streamed candidate-window slots that held a real
neighbour, in the 4-clique cell."""
from span_readers import window_fill as read  # noqa: F401
