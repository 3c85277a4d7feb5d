"""Work over the window: args {"key": <samples key of the amount>,
"per_chip": bool}. All the work and all the time of the window."""


def read(ctx, args):
    s = ctx["samples"]
    if args["key"] not in s or not s.get("window_s"):
        return None
    rate = s[args["key"]] / s["window_s"]
    return rate / ctx["chips"] if args.get("per_chip") else rate
