"""Share of the device's op time, over every program of the traced part,
of the ops whose ``jax.named_scope`` path holds any of args["scopes"]
(``attn_cached``; ``mlp``; a later family's ``experts``): where the
traced part ran several programs, each op's scope from its own program's
map, joined by (program, instruction) (trace_programs.py). 0.0 where the
maps hold none of the names; nothing where the program gives no maps."""

from benchmarks import spec, trace_programs


def read(ctx, args):
    trace, maps = ctx.get("trace"), ctx.get("scopes")
    if trace is None or not trace.chips:
        return None
    if maps is None:
        return spec.NotRead("the engine has no compiled_programs()")
    programs = trace_programs.of(trace)
    total = trace_programs.op_seconds(programs)
    under = trace_programs.scope_seconds(programs, maps, args["scopes"])
    return 100.0 * under / total if total else None
