"""A statistic of the client's request log that the cell holds to no bound:
the arithmetic of an end-to-end metric (`stats.end_to_end`), read in the
traced run. params: stat (as in an end_to_end file)."""

import stats


def read(ctx, params):
    value, _ = stats.end_to_end({"stat": params["stat"]}, ctx["requests"], ctx["window"],
                                ctx["worst_ms"])
    return value
