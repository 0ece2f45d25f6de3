"""100 * device time of the programs (XLA modules) whose names match / the
device's busy time in the traced interval. The step programs carry names
(`jit_pstpu_step...` is prefill, `jit_pstpu_multi_step_k<n>...` a decode burst);
where none matches, as in a program that names nothing, there is nothing to
read. params: patterns [regex of module names]."""

from readers_common import matching


def read(ctx, params):
    tr = ctx.get("trace")
    if not tr or tr["busy_s"] <= 0:
        return None
    hit = matching(tr["modules"], params["patterns"])
    if not hit:
        return None
    return 100.0 * sum(v[1] for v in hit.values()) / tr["devices"] / tr["busy_s"]
