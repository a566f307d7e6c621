"""Model: the fullest expert's rows over the mean expert's, over the expert
layers and token steps of the window's decode blocks (``moe_rows_max_expert``
x experts / ``moe_rows_routed``): 1 is a perfectly even router."""

from chipbench.metrics._scopes import counter_delta


def read(run):
    got = counter_delta(run, "moe_rows_max_expert", "moe_rows_routed")
    if got is None or got[1] <= 0:
        return None
    return got[0] * run.cell.config["num_experts"] / got[1]
