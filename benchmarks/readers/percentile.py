"""A percentile of a list of samples: args {"key": ..., "q": 95}."""

import numpy as np


def read(ctx, args):
    values = ctx["samples"].get(args["key"])
    if not values:
        return None
    return float(np.percentile(np.asarray(values, float), args["q"]))
