"""``ffn_col_util.offline``: of the columns the Segment SpMM kernels computed
in the window, the share that held a real token — the window's change in
``Engine.counters()``' ``spmm_cols_useful`` over that in
``spmm_cols_computed``.  Free slots, a prompt chunk's padding to its bucket
and the executor's padding of N to a whole 128-wide tile are the rest."""


def read(run):
    computed = run.counters.get("spmm_cols_computed")
    if not computed:
        return None
    return 100.0 * run.counters["spmm_cols_useful"] / computed
