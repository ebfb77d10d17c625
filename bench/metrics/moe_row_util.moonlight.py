"""``moe_row_util.moonlight``: of the rows the grouped expert GEMM computed
in the window, the share that held a routed token — the window's change in
``Engine.counters()``' ``moe_rows_routed`` over that in
``moe_rows_computed``.  The rest is each held expert's last chunk padded to
whole rows."""


def read(run):
    computed = run.counters.get("moe_rows_computed")
    if not computed:
        return None
    return 100.0 * run.counters["moe_rows_routed"] / computed
