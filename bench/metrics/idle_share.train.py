"""Idle share of the device in the traced window, in percent."""
from bench.readers import idle_share as read  # noqa: F401
