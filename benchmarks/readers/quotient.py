"""One recorded total over another: args {"num": ..., "den": ...,
"scale": 1.0}."""


def read(ctx, args):
    s = ctx["samples"]
    if args["num"] not in s or not s.get(args["den"]):
        return None
    return args.get("scale", 1.0) * s[args["num"]] / s[args["den"]]
