"""``idle_share.spgemm``: Device idle share of the traced window."""
from harness.readers import idle_share as read  # noqa: F401
