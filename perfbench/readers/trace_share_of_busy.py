"""Device time of the operations whose names match, as a share (%) of the
device's busy time in the traced sub-window. params: patterns [regex of
operation names]. None where nothing matches."""

from readers_common import matching


def read(ctx, params):
    tr = ctx.get("trace")
    if not tr or tr["busy_s"] <= 0:
        return None
    hit = matching(tr["ops"], params["patterns"])
    if not hit:
        return None
    return 100.0 * sum(v[1] for v in hit.values()) / tr["devices"] / tr["busy_s"]
