"""``admit_share.offline``: Share of the window inside Engine.admit_pending."""
from harness.readers import admit_share as read  # noqa: F401
