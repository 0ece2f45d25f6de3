"""100 * (sum of counter deltas) / (sum of counter deltas) over the window,
from `GET /stats`. params: num [names], den [names]."""


def read(ctx, params):
    s0, s1 = ctx["snap0"]["stats"], ctx["snap1"]["stats"]
    delta = lambda names: sum(s1[n] - s0.get(n, 0) for n in names if n in s1)  # noqa: E731
    den = delta(params["den"])
    return 100.0 * delta(params["num"]) / den if den > 0 else None
