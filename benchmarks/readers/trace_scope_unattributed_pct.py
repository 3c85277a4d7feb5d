"""Share of the device's op time that nothing names: the op carries none
of args["known"] scopes and is no kernel or collective by its own name
(args["named"]). 100 where the program carries no scopes. For cells
whose traced part runs one program (scopes.py says why)."""

from benchmarks import scopes


def read(ctx, args):
    trace = ctx.get("trace")
    if trace is None or not trace.chips:
        return None
    share = scopes.unattributed_share(
        trace, scopes.train_step_scopes(ctx["cell"]), args["known"],
        args["named"])
    return None if share is None else 100.0 * share
