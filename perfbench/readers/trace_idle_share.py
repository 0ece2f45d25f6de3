"""100 * (1 - union of device-operation intervals / traced sub-window)."""


def read(ctx, params):
    tr = ctx.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
