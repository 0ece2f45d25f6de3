"""Median device duration, in ms, of the programs (XLA modules) whose names
match. The step programs carry stable names (`jit_pstpu_step*` is prefill,
`jit_pstpu_multi_step_k<k>*` a decode burst), so a program is found without a
kernel inside it. params: patterns [regex of module names]. None where no
module matches."""

from readers_common import matching
from tracereduce import merge


def read(ctx, params):
    tr = ctx.get("trace")
    if not tr:
        return None
    rows = matching(tr.get("modules", {}), params["patterns"])
    if not rows:
        return None
    return merge(rows)[2] * 1000.0
