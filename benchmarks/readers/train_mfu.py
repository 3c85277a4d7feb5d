"""Model FLOP/s utilization: tokens/s x required FLOPs per token (causal
attention once, no recompute) over chips x the table's bf16 peak."""

from benchmarks import flops


def read(ctx, args):
    s = ctx["samples"]
    if "tokens_in_window" not in s or not s.get("window_s"):
        return None
    per_token = flops.train_flops_per_token(ctx["cell"]["hp"], s["seq"])
    achieved = s["tokens_in_window"] / s["window_s"] * per_token
    return 100.0 * achieved / (ctx["chips"] * ctx["peak"]["bf16_flops_per_s"])
