"""``setup_s``: Set-up: process start to the window's start (loading,
weights, planning, warm-up, compiles)."""
from harness.readers import setup_s as read  # noqa: F401
