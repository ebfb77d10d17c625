"""``decode_step_ms.offline``: median device time of one run of the Engine's
decode program (``jit_engine_decode``, line ``XLA Modules``) that lies
wholly inside the traced window."""
import statistics


def read(run):
    prog = run.extra.get("program")
    runs = prog.module_runs.get("jit_engine_decode") if prog else None
    return 1e3 * statistics.median(runs) if runs else None
