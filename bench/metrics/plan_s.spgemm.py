"""``plan_s.spgemm``: Host seconds in plan_matmul during set-up."""
from harness.readers import plan_s as read  # noqa: F401
