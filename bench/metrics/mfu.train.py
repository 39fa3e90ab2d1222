"""Model FLOPs utilization of the ticks or steps in the traced window."""
from bench.readers import mfu as read  # noqa: F401
