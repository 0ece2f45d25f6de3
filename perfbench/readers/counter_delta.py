"""Change of one counter over the window. params: name, from ("stats" |
"metrics": the JSON of /stats or a Prometheus series of /metrics)."""

from readers_common import prom


def read(ctx, params):
    if params.get("from", "stats") == "metrics":
        return (prom(ctx["snap1"]["metrics"], params["name"])
                - prom(ctx["snap0"]["metrics"], params["name"]))
    s0, s1 = ctx["snap0"]["stats"], ctx["snap1"]["stats"]
    if params["name"] not in s1:
        return None
    return float(s1[params["name"]] - s0.get(params["name"], 0))
