"""Model: experts that got a row or more, of all experts, over the expert
layers and token steps of the window's decode blocks (the engine's counters
``moe_experts_touched`` / ``moe_layer_steps``)."""

from chipbench.metrics._scopes import counter_delta


def read(run):
    got = counter_delta(run, "moe_experts_touched", "moe_layer_steps")
    if got is None or got[1] <= 0:
        return None
    return 100.0 * got[0] / (run.cell.config["num_experts"] * got[1])
