"""``segment_share.offline``: Mosaic (Pallas) kernel time over the traced
window; on the model path these are the Segment SpMM kernels alone."""
from harness.readers import mosaic_share as read  # noqa: F401
