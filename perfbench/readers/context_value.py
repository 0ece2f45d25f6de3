"""A number the harness itself counted in the run and handed to the readers
under a key of its own (`windows_voided`). params: key."""


def read(ctx, params):
    value = ctx.get(params["key"])
    return None if value is None else float(value)
