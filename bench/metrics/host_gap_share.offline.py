"""``host_gap_share.offline``: device idle time whose innermost program span
is the Engine's own host work in a decode step — ``segfold.engine.prepare``
(token and position arrays and their copy), ``.dispatch`` (the decode call
until it returns) or ``.update`` (slot bookkeeping) — over the traced
window.  Idle under ``segfold.engine.sync`` is the device finishing the
step late, not the host holding it back, and is left out."""
HOST_WORK = ("segfold.engine.prepare", "segfold.engine.dispatch",
             "segfold.engine.update")


def read(run):
    prog = run.extra.get("program")
    idle = prog.idle_in(HOST_WORK) if prog else None
    return None if idle is None else 100.0 * idle / run.window_s
