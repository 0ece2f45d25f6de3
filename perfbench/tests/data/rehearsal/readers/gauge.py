"""A Prometheus gauge of /metrics at the window's end (the traced run resets
the sample windows at its start). params: name, labels {k: v}."""

from readers_common import prom


def read(ctx, params):
    text = ctx["snap1"]["metrics"]
    if params["name"] not in text:
        return None
    return prom(text, params["name"], **params.get("labels", {}))
