"""Device time per traced step of the ops whose ``jax.named_scope`` path
holds any of args["scopes"] (``mlp``; ``head`` and ``ce``;
``rematted_computation``, which JAX adds around remat's second forward),
mean over the chips. For cells whose traced part runs one program
(scopes.py says why). 0 where the program carries no scopes."""

from benchmarks import scopes


def read(ctx, args):
    trace, steps = ctx.get("trace"), ctx["samples"].get("traced_steps")
    if trace is None or not trace.chips or not steps:
        return None
    found = scopes.train_step_scopes(ctx["cell"])
    return 1e3 * scopes.scope_seconds(trace, found, args["scopes"]) / steps
