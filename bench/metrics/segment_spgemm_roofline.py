"""``segment_spgemm_roofline``: Least time over Segment SpGEMM kernel time."""
from harness.readers import segment_spgemm_roofline as read  # noqa: F401
