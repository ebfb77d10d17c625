"""``spgemm_pass_ms``: Window over complete SpGEMM passes."""
from harness.readers import spgemm_pass_ms as read  # noqa: F401
