"""``mfu.offline``: Model FLOPs of the window's tokens per second over the
bf16 peak."""
from harness.readers import mfu as read  # noqa: F401
