"""100 * (sum of series deltas) / (sum of series deltas) over the window, from
`GET /metrics`: each series summed over its label sets, so a counter that
`/stats` gives as a dictionary by label (`queued_ahead_dispatches_total` by
kind, `queue_ahead_drains_total` by reason) is read as one number. params:
num [series names], den [series names]. None where the program exposes none of
the denominator's series, or nothing was counted in the window."""

from readers_common import prom


def read(ctx, params):
    m0, m1 = ctx["snap0"]["metrics"], ctx["snap1"]["metrics"]
    if not any(name in m1 for name in params["den"]):
        return None
    delta = lambda names: sum(prom(m1, n) - prom(m0, n) for n in names)  # noqa: E731
    den = delta(params["den"])
    return 100.0 * delta(params["num"]) / den if den > 0 else None
