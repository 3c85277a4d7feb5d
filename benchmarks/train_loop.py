"""A training cell: ``JaxTrainer(train_loop).fit()`` fed by a
``ray_tpu.data`` Dataset, measured by this file's own loop.

``run`` is the runner's side (it never touches a device); ``train_loop``
runs in the JaxTrainer worker that owns the cell's chips, which is also
the only process that can trace them, so it takes the trace, reduces it
and sends back numbers.
"""

from __future__ import annotations

import math
import statistics
import tempfile
import time

from . import holder, spec, traffic

# how many of the probe's last positions leave the first-forward program
# as logits (server.py compares logits too); the per-token NLL leaves it
# for every position
LAST_LOGITS = 256


def token_nll(logits, targets):
    """(S, V) float32 logits, (S,) targets -> (S,) next-token NLL."""
    import jax

    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jax.numpy.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]


def first_forward(family, cfg, mesh, hp, params, probe, tokens) -> dict:
    """The program's first forward against the float32 reference, per
    token: ``tokens`` (B, S + 1) is the sequence ``probe`` repeated, as
    the step is given it (so it divides over the mesh and a kernel runs
    in its shard_map as in the step). One reference pass over the one
    sequence; one jitted program through ``family.logits``, which
    reduces to row 0's NLL (S,) and the logits of its last positions,
    so nothing of size B x S x V leaves it. The mean of a few thousand
    NLLs hides a sublayer in fp8 at any limit (PR 27's refusal); their
    root mean square difference does not."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    logits_fn = getattr(family, "logits", None)
    if logits_fn is None:
        raise ValueError(
            f"{family.__name__} gives no logits(params, tokens, config): a "
            "train cell's `correct` compares the program's first forward per "
            "token with reference_logits (README.md, \"A family\")")
    probe = jnp.asarray(probe)
    last = min(LAST_LOGITS, probe.shape[0] - 1)
    replicated = NamedSharding(mesh, PartitionSpec())

    # three programs, not a dozen small ones compiled anew in every run:
    # the reference's reduction, the program's forward, the comparison
    @partial(jax.jit, out_shardings=replicated)
    def reduced(logits, targets):
        return token_nll(logits, targets), logits[-last:]

    @partial(jax.jit, out_shardings=replicated)
    def forward(params, tokens):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            row = logits_fn(params, tokens[:, :-1], cfg)[0]
        return reduced(row, tokens[0, 1:])

    @jax.jit
    def compared(got_nll, got_last, want_nll, want_last):
        def rms(x):
            return jnp.sqrt(jnp.mean(jnp.square(x)))

        # how the differences are spread, beside their RMS: a routed
        # family's outliers (a near-tied choice flipped) show here
        # before a limit is set; they decide nothing
        gap = jnp.abs(got_nll - want_nll)
        return {
            "nll_rms": rms(got_nll - want_nll),
            "nll_abs_median": jnp.median(gap),
            "nll_abs_p80": jnp.percentile(gap, 80),
            "nll_abs_max": gap.max(),
            "nll_mean": got_nll.mean(),
            "reference_nll_mean": want_nll.mean(),
            "last_logits_rel_rms": rms(got_last - want_last) / rms(want_last),
            "finite": jnp.isfinite(got_nll).all(),
        }

    # the reference's (S, V) logits are gone before the forward runs
    want = reduced(family.reference_logits(params, probe[:-1], hp), probe[1:])
    out = jax.device_get(compared(*forward(params, tokens), *want))
    return {k: bool(v) if k == "finite" else float(v) for k, v in out.items()}


def step_scalars(metrics: dict) -> dict:
    """What a step reports besides its loss, as it came (device values:
    read after the window, never inside it)."""
    return {k: v for k, v in metrics.items()
            if k != "loss" and getattr(v, "ndim", None) == 0}


# ------------------------------------------------------------ the worker
def train_loop(config: dict) -> None:
    from functools import partial

    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from ray_tpu import parallel, train
    from ray_tpu.parallel.train_step import state_shardings

    cell, rehearse = config["cell"], config["rehearse"]
    hp, tr, opts = cell["hp"], cell["traffic"], cell["train"]
    family = spec.family_of(hp)
    phases = {"loop_entered": time.time()}  # where set-up goes, wall clock
    device = holder.device_info(rehearse)
    phases["chips_open"] = time.time()
    compiles = holder.CompileCounter()
    devices = jax.devices()
    n = len(devices)
    cfg = family.model_config(hp, opts)
    mesh = parallel.make_mesh(devices=devices)  # every chip on fsdp
    opt = parallel.default_optimizer(
        opts["learning_rate"], warmup_steps=opts["warmup_steps"],
        total_steps=opts["total_steps"])
    specs = family.param_specs(cfg)
    # parallel.create_train_state closes over its key, which makes the
    # key a constant of the init program: every seed would compile it
    # anew (26 s of set-up on the chip, PR 23). Same construction here,
    # with the key as an argument.
    def init(key):
        params = family.init_params(key, cfg)
        return parallel.TrainState(
            jax.numpy.zeros((), jax.numpy.int32), params, opt.init(params))

    state_sh, _ = state_shardings(
        mesh, specs, partial(init, jax.random.PRNGKey(0)))
    state = jax.jit(init, out_shardings=state_sh)(
        spec.prng_key(config["seed"]))
    jax.block_until_ready(state)
    phases["state_made"] = time.time()
    step = parallel.make_train_step(
        partial(family.loss_fn, config=cfg), opt, mesh, state_sh)

    seq, batch_size = tr["seq"], tr["seqs_per_chip"] * n
    tokens_per_step = batch_size * seq
    shard = train.get_dataset_shard("train")

    def batches():
        while True:  # a new epoch when the blocks run out
            yield from shard.iter_batches(
                batch_size=batch_size,
                device_put=parallel.batch_sharding(mesh))

    feed = batches()

    # -- correct, part 1: the first forward against the float32
    # reference, on one seeded sequence: per token (first_forward), and
    # the step's first loss against the reference's (the probe batch is
    # that sequence repeated, so the step's mean loss is the sequence's
    # loss; a family whose loss carries more than cross entropy gives
    # its own reference_loss). Before any step: the step donates the
    # state it is given.
    t0 = time.perf_counter()
    probe = traffic.probe_sequence(config["seed"], seq + 1, hp["vocab_size"])
    probe_batch = {"tokens": jax.device_put(
        np.ascontiguousarray(np.broadcast_to(probe, (batch_size, seq + 1))),
        parallel.batch_sharding(mesh))}
    first = first_forward(
        family, cfg, mesh, hp, state.params, probe, probe_batch["tokens"])
    reference_loss = (
        float(family.reference_loss(
            state.params, jax.numpy.asarray(probe), hp))
        if hasattr(family, "reference_loss") else first["reference_nll_mean"])
    reference_s = time.perf_counter() - t0
    phases["reference_done"] = time.time()
    # compiled so that its text is sure to carry this build's scopes
    # (compile_with_scopes says why): the traced part joins the trace
    # with the text of the very program it ran. A program from before
    # the scopes has no such function, and its per-layer metrics by
    # scope read nothing.
    try:
        from ray_tpu._private.jax_utils import compile_with_scopes, scope_map
    except ImportError:
        compile_with_scopes = scope_map = None
    lowered = step.lower(state, probe_batch)
    compiled = (compile_with_scopes(lowered) if compile_with_scopes
                else lowered.compile())
    text = compiled.as_text()
    kernels_in_program = all(k in text for k in family.KERNELS)
    phases["step_compiled"] = time.time()

    losses = []
    state, metrics = compiled(state, probe_batch)
    losses.append(float(metrics["loss"]))
    # one step off the Dataset, so the feed's first batch is not timed
    state, metrics = compiled(state, next(feed))
    losses.append(float(metrics["loss"]))

    # every parameter the specs shard: one 1/k shard on each device
    sharded = whole = 0
    for leaf, pspec in zip(
            jax.tree.leaves(state.params),
            jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))):
        k = 1
        for axes in pspec:
            for axis in (axes,) if isinstance(axes, str) else (axes or ()):
                k *= mesh.shape[axis]
        if k == 1:
            continue
        shards = leaf.addressable_shards
        if ({s.device for s in shards} == set(devices)
                and all(s.data.size * k == leaf.size for s in shards)):
            sharded += 1
        else:
            whole += 1

    # ------------------------------------------------------- the window
    # the Dataset's own seconds and counts (data.stage_batch in the
    # prefetch thread, data.next_batch); a shard from before them has none
    iter_stats = getattr(shard, "iter_stats", None)
    stats_before = iter_stats.snapshot() if iter_stats is not None else None
    compiles_before = compiles.count
    step_ms, wait_ms, kept = [], [], []
    window_start_wall = time.time()
    start = time.perf_counter()
    elapsed = 0.0
    while elapsed < config["seconds"]:
        t0 = time.perf_counter()
        batch = next(feed)
        t1 = time.perf_counter()
        state, metrics = compiled(state, batch)
        losses.append(float(metrics["loss"]))  # waits for the step
        t2 = time.perf_counter()
        kept.append(step_scalars(metrics))
        wait_ms.append(1e3 * (t1 - t0))
        step_ms.append(1e3 * (t2 - t1))
        elapsed = t2 - start
    compiled_in_window = compiles.count - compiles_before
    stats_after = iter_stats.snapshot() if iter_stats is not None else None
    host_kept = jax.device_get(kept)
    samples = {
        **holder.phase_deltas(stats_before, stats_after),
        "window_s": elapsed,
        "steps": len(step_ms),
        "tokens_in_window": len(step_ms) * tokens_per_step,
        "seq": seq, "seqs_per_step": batch_size,
        "step_ms": step_ms, "input_wait_ms": wait_ms,
        # step.<name>: every other scalar the step reports, per step
        **{f"step.{k}": [float(m[k]) for m in host_kept]
           for k in (host_kept[0] if host_kept else {})},
    }

    # -------------------------------------------- the traced part, after
    per_layer, not_read, traced, breakdown = {}, {}, {}, None
    if config["trace"]:
        tracer = holder.Tracer()
        tracer.start()
        for _ in range(opts["trace_steps"]):
            with jax.profiler.TraceAnnotation("bench.input_wait"):
                batch = next(feed)
            with jax.profiler.TraceAnnotation("bench.step"):
                state, metrics = compiled(state, batch)
                losses.append(float(metrics["loss"]))
        tracer.stop()
        trace = tracer.reduce()
        samples["traced_steps"] = opts["trace_steps"]
        traced = holder.traced_device_block(trace)
        breakdown = holder.trace_reduce.breakdown(trace)
        per_layer, not_read = spec.evaluate(config["per_layer"], {
            "cell": cell, "chips": n, "samples": samples, "trace": trace,
            "scopes": {"step": scope_map(text)} if scope_map else None,
            "peak": spec.peak_for(device["kind"], rehearse)})

    train.report({
        "device": {**device, "memory_peak_bytes": holder.memory_peak_bytes(),
                   **traced},
        "window_start_wall": window_start_wall, "phases": phases,
        "samples": samples, "losses": losses,
        "reference_loss": reference_loss, "reference_s": reference_s,
        "first_forward": first,
        "kernels_in_program": kernels_in_program,
        "compiled_in_window": compiled_in_window,
        "params_sharded": sharded, "params_not_sharded": whole,
        "per_layer": per_layer, "metrics_not_read": not_read,
        "breakdown": breakdown,
    })


# ------------------------------------------------------------ the runner
def run(cell: dict, args, per_layer: dict) -> dict:
    import ray_tpu.data
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    hp, tr, opts = cell["hp"], cell["traffic"], cell["train"]
    n = cell["chips"]
    phases = {"runner_ready": time.time()}
    distinct = traffic.corpus(args.seed, tr["corpus_batches"],
                              tr["seqs_per_chip"] * n, tr["seq"],
                              hp["vocab_size"])
    dataset = ray_tpu.data.from_numpy(
        [distinct[i % len(distinct)] for i in range(tr["dataset_blocks"])],
        column="tokens")
    scaling = (ScalingConfig() if args.rehearse else
               ScalingConfig(use_tpu=True, tpu_chips_per_worker=n))
    phases["dataset_made"] = time.time()
    with tempfile.TemporaryDirectory(prefix="bench_train_") as storage:
        result = JaxTrainer(
            train_loop,
            train_loop_config={
                "cell": cell, "seed": args.seed, "seconds": args.seconds,
                "trace": bool(args.trace), "rehearse": args.rehearse,
                "per_layer": per_layer},
            scaling_config=scaling,
            run_config=RunConfig(name="bench", storage_path=storage),
            datasets={"train": dataset},
        ).fit()
    if result.error is not None:  # fit() returns the error
        raise RuntimeError(f"the train loop failed: {result.error!r}")
    if not result.metrics:
        raise RuntimeError("the train loop ended without its report")
    r = dict(result.metrics)
    phases.update(r["phases"], window=r["window_start_wall"])

    losses, first = r["losses"], r["first_forward"]
    uniform = math.log(hp["vocab_size"])
    last_quarter = losses[-max(len(losses) // 4, 1):]
    checks = {
        "losses_finite": all(math.isfinite(x) for x in losses),
        # unit-variance logits over V classes cost about ln V + 1/2
        "first_loss_near_ln_vocab":
            uniform - 0.1 <= losses[0] <= uniform + 1.1,
        "loss_fell": statistics.median(last_quarter)
            <= losses[0] - opts["loss_drop_min"],
        "first_loss_matches_reference":
            abs(losses[0] - r["reference_loss"]) <= opts["reference_loss_tol"],
        "first_nll_matches_reference": first["finite"]
            and first["nll_rms"] <= opts["reference_nll_rms_tol"],
        "kernels_in_program": bool(r["kernels_in_program"]),
        "nothing_compiled_in_window": r["compiled_in_window"] == 0,
        "every_sharded_param_split": r["params_not_sharded"] == 0
            and (n <= 1 or r["params_sharded"] > 0),
    }
    steps = r["samples"]["steps"]
    return {
        "checks": checks,
        "attempted": steps,
        # losses: the probe step, one warm step, then the window's
        "failed": sum(not math.isfinite(x) for x in losses[2:2 + steps]),
        "samples": r["samples"],
        "window_start_wall": r["window_start_wall"],
        "device": r["device"],
        "per_layer": r["per_layer"],
        "metrics_not_read": r["metrics_not_read"],
        "breakdown": r["breakdown"],
        "notes": {
            # seconds from the runner's start to the end of each phase
            "setup_phases_s": {k: round(v - args.process_start, 3)
                               for k, v in phases.items()},
            "losses_first_last": [losses[0], losses[1], losses[-1]],
            "loss_last_quarter_median": statistics.median(last_quarter),
            "reference_loss": r["reference_loss"],
            "reference_s": r["reference_s"],
            "first_forward": first,
            # each number compared, beside its limit
            "compared": {
                "first_loss_minus_reference": [
                    abs(losses[0] - r["reference_loss"]),
                    opts["reference_loss_tol"]],
                "first_nll_rms": [
                    first["nll_rms"], opts["reference_nll_rms_tol"]]},
            "params_sharded": r["params_sharded"],
            # step.<name>: how many, the first and the last of each
            "step_samples": {k: [len(v), v[0], v[-1]]
                             for k, v in r["samples"].items()
                             if k.startswith("step.") and v},
            "step_ms_median": statistics.median(r["samples"]["step_ms"]),
            "input_wait_ms_median":
                statistics.median(r["samples"]["input_wait_ms"]),
        },
    }
