"""A number the run recorded, as it is: args {"key": <samples key>}."""


def read(ctx, args):
    return ctx["samples"].get(args["key"])
