"""Model: of the picks the routers made in the window's decode blocks (rows
x experts a token, over expert layers and token steps: the engine's
``moe_picks``), the share that went to an expert held here
(``moe_rows_routed``). 100 where the layers hold every expert; 12.5 on even
routing where they hold an eighth."""

from chipbench.metrics._scopes import counter_delta


def read(run):
    got = counter_delta(run, "moe_rows_routed", "moe_picks")
    if got is None or got[1] <= 0:
        return None
    return 100.0 * got[0] / got[1]
