"""``moe_share.moonlight``: device time of the grouped expert GEMM kernels
(named ``moe_gemm*``) over the traced window."""
from harness.trace import kernel_time


def read(run):
    if run.trace is None:
        return None
    kernel = kernel_time(run.trace, lambda k: k.startswith("moe_gemm"))
    return 100.0 * kernel / run.trace.window_s if kernel > 0 else None
