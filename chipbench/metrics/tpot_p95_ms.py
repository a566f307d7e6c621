"""Scheduler and admission: per request (t_last - t_first) / (tokens - 1)
on the benchmark's clock, 95th percentile over every request with two
tokens or more on the host when the window closed, finished or not. In a
closed loop at full batch it is the step time plus the request's share of
the prefills that took turns with it, so it stands among the layers here;
a cell below capacity reports it end to end."""

from chipbench.harness.clock import percentile
from chipbench.harness.serving import tpots_ms


def read(run):
    values = tpots_ms(run.window)
    return percentile(values, 95) if values else None
