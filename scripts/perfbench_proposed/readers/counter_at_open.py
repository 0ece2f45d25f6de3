"""A counter of `GET /stats` as it stood when the window opened: what the
set-up (process start to the window's opening) accumulated. params: name."""


def read(ctx, params):
    value = ctx["snap0"]["stats"].get(params["name"])
    return None if value is None else float(value)
