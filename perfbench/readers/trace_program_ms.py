"""Median device duration, in ms, of the step programs that hold a kernel.
The jitted step programs carry no stable names yet (their XLA modules are all
`jit__unknown(<fingerprint>)`), their kernels do: a program run is found by
the custom call inside it. params: kernels [names as tracereduce cuts them]."""

from tracereduce import merge


def read(ctx, params):
    tr = ctx.get("trace")
    if not tr:
        return None
    rows = {k: v for k, v in tr.get("programs_with", {}).items() if k in params["kernels"]}
    if not rows:
        return None
    return merge(rows)[2] * 1000.0
