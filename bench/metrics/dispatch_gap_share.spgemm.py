"""``dispatch_gap_share.spgemm``: device idle time inside ``segfold.execute``
spans — ``execute_plan``'s own host work, and its kernel launch
(``segfold.execute.launch``) — over the traced window."""
EXECUTE = ("segfold.execute", "segfold.execute.launch")


def read(run):
    prog = run.extra.get("program")
    idle = prog.idle_in(EXECUTE) if prog else None
    return None if idle is None else 100.0 * idle / run.window_s
