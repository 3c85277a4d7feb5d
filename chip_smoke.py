"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py

drives the two main paths once, through the entry points a user calls,
at the full LLAMA_BENCH width (16 layers, 2048 wide, 16x128 heads, 8 KV
heads, vocabulary 32000, bf16 parameters, remat, flash attention) with
random weights from a seed:

  kernels  a ``@ray_tpu.remote(num_tpus=1)`` task compiles every Pallas
           kernel in ray_tpu/ops at the train shapes and compares it
           with the float32 XLA formulation at "highest" precision;
  train    ``JaxTrainer(...).fit()``: one worker that owns every local
           chip, ``parallel.make_train_step`` over a mesh of them, fed
           by a ``ray_tpu.data`` shard staged with
           ``iter_batches(device_put=...)``;
  serve    ``serve.run(build_llm_app(LLMConfig(...)))`` with the HTTP
           proxy on, one replica per chip, requests through the handle
           and one over HTTP.

Each phase runs in a process of its own that the scheduler gives the
chip(s) and that gives them back by exiting, so the hand-over between
phases is under test too. This driver never initialises a JAX backend:
a parent that holds the chip leaves none for its workers.

It exits non-zero if a phase fails or finds itself on the CPU, or if
``ray_tpu.shutdown()`` leaves one of the processes it started behind
(any such is killed before this one exits), and without an accelerator
it fails at once and says why. Every phase
prints one JSON line (device, pid, wall seconds split into compile and
run: information, not a metric); the last line of a passing run is
``{"ok": true, "device": {...}}``.

    python chip_smoke.py --rehearse-on-cpu

walks the same control flow at toy shapes on the CPU, to debug the
script before chip time is spent. It proves nothing about the chip.
"""

from __future__ import annotations

import argparse
import ctypes
import dataclasses
import json
import os
import signal
import sys
import threading
import time
import urllib.request

SEED = 0
TRAIN_STEPS = 8
MAX_TOKENS = 32
DEADLINE_S = 1100  # the caller allows 1200, compilation included

# Kernel parity. Operands are bf16, accumulation float32, and the
# reference is float32 at "highest" precision on the same operands, so
# what separates them is bf16 rounding of the probabilities and of the
# results: about 2^-9 relative. On a v5e the flash kernel measured
# 2.0e-3 to 3.0e-3 relative RMS error and at most 0.5% of the largest
# reference value (chip run, 2026-09-26); the same arithmetic written in
# plain XLA with bf16 operands measured 3.7e-3 to 4.2e-3. A causal mask
# off by one costs at least 1.7e-1 relative RMS and one skipped block of
# ten 1.3e-1 and 4.7% of the largest value (float32, S=2048), and fp8
# operands about 6e-2, so these bounds pass the stated precision with a
# margin under 3x and fail every such defect by more than 10x.
REL_RMS_TOL = 8e-3
MAX_ABS_TOL = 2 ** -6  # of the largest reference magnitude
# Per-token losses come out in float32 from float32 accumulation;
# logits rounded to bf16 on the way would be off by about 1e-2.
CE_LOSS_TOL = 2e-3


@dataclasses.dataclass(frozen=True)
class Shapes:
    """What a run is sized by. ``chip`` is the contract; ``toy`` only
    exists so that the control flow can be rehearsed on the CPU."""

    model: str  # name of a config in ray_tpu.models.llama
    seqs_per_chip: int
    seq: int
    max_seq: int
    prompt_lens: tuple  # (shortest, longest)
    loss_drop: float  # least fall of the loss over TRAIN_STEPS steps
    model_overrides: tuple = ()  # (field, value) pairs

    def config(self, **overrides):
        import jax.numpy as jnp

        from ray_tpu.models import llama

        return dataclasses.replace(
            getattr(llama, self.model), param_dtype=jnp.bfloat16,
            **dict(self.model_overrides), **overrides,
        )


# r01/r02 (2026-07-29, one chip) fell from ln 32000 to 5.19 in eleven
# steps on one repeated batch; a whole nat in eight is "clearly below".
CHIP = Shapes("LLAMA_BENCH", seqs_per_chip=8, seq=2048, max_seq=2048,
              prompt_lens=(64, 512), loss_drop=1.0)
# one 128-wide head, so that "flash" is the kernel in the rehearsal too
TOY = Shapes("LLAMA_TINY", seqs_per_chip=2, seq=128, max_seq=256,
             prompt_lens=(8, 100), loss_drop=0.1,
             model_overrides=(("n_heads", 1), ("n_kv_heads", 1)))
FLASH_KERNELS = ("flash_attention_fwd", "flash_attention_bwd_dkv",
                 "flash_attention_bwd_dq")
CE_KERNELS = ("fused_ce_fwd", "fused_ce_bwd_dx", "fused_ce_bwd_dw")


# ---------------------------------------------------------------- kernels
def kernels_phase(shapes: Shapes) -> dict:
    """Runs in a worker that was given one chip."""
    import jax
    import jax.numpy as jnp

    from ray_tpu._private.jax_utils import device_report
    from ray_tpu.models import llama
    from ray_tpu.ops import pallas_attention, pallas_ce
    from ray_tpu.ops.attention import flash_attention
    from ray_tpu.ops.pallas_ce import fused_cross_entropy

    report = device_report()
    report["interpreted"] = pallas_attention._interpret() or pallas_ce._interpret()
    cfg = shapes.config()
    B, S = shapes.seqs_per_chip, shapes.seq
    H, KVH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    keys = jax.random.split(jax.random.PRNGKey(SEED), 8)

    def normal(key, shape, scale=1.0):
        x = jax.random.normal(key, shape, jnp.float32) * scale
        return x.astype(jnp.bfloat16)

    def errors(got, want):
        got, want = got.astype(jnp.float32), want.astype(jnp.float32)
        err = jnp.abs(got - want)
        return {
            "finite": bool(jnp.isfinite(got).all()),
            "rel_rms": float(
                jnp.sqrt(jnp.mean(err ** 2)) / jnp.sqrt(jnp.mean(want ** 2))
            ),
            "max_abs_over_absmax": float(err.max() / jnp.abs(want).max()),
        }

    def close(e):
        return (
            e["finite"]
            and e["rel_rms"] <= REL_RMS_TOL
            and e["max_abs_over_absmax"] <= MAX_ABS_TOL
        )

    def compile_and_run(fn, *args):
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        t1 = time.perf_counter()
        out = jax.block_until_ready(compiled(*args))
        return out, compiled.as_text(), t1 - t0, time.perf_counter() - t1

    compile_s = run_s = 0.0
    checks = {}

    # flash attention, forward and backward, at the train shapes
    q = normal(keys[0], (B, S, H, hd))
    k = normal(keys[1], (B, S, KVH, hd))
    v = normal(keys[2], (B, S, KVH, hd))
    do = normal(keys[3], (B, S, H, hd))

    def flash(q, k, v, do):
        o, vjp = jax.vjp(lambda *a: flash_attention(*a, causal=True), q, k, v)
        return (o, *vjp(do))

    @jax.jit
    def flash_reference(q, k, v, do):
        # two sequences at a time: the float32 logits of all of them
        # at once would not leave room for their gradients
        with jax.default_matmul_precision("highest"):
            f32 = [x.astype(jnp.float32) for x in (q, k, v, do)]
            o, vjp = jax.vjp(
                lambda *a: llama._attention_xla(*a, cfg), *f32[:3]
            )
            return (o, *vjp(f32[3]))

    got, text, c, r = compile_and_run(flash, q, k, v, do)
    compile_s, run_s = compile_s + c, run_s + r
    checks["flash_kernels_in_program"] = all(n in text for n in FLASH_KERNELS)
    want = [
        flash_reference(*(x[i:i + 2] for x in (q, k, v, do)))
        for i in range(0, B, 2)
    ]
    want = [jnp.concatenate([w[j] for w in want]) for j in range(4)]
    for name, g, w in zip(("o", "dq", "dk", "dv"), got, want):
        e = errors(g, w)
        report[f"flash_{name}"] = e
        checks[f"flash_{name}"] = close(e)
    del got, want, q, k, v, do

    # fused lm-head cross entropy, forward and backward
    N, D, V = B * S, cfg.dim, cfg.vocab_size
    x = normal(keys[4], (N, D))
    w = normal(keys[5], (D, V), scale=D ** -0.5)
    targets = jax.random.randint(keys[6], (N,), 0, V, dtype=jnp.int32)
    g = normal(keys[7], (N,)).astype(jnp.float32)

    def fused(x, w, targets, g):
        loss, vjp = jax.vjp(lambda a, b: fused_cross_entropy(a, b, targets), x, w)
        return (loss, *vjp(g))

    @jax.jit
    def ce_reference(x, w, targets, g):
        with jax.default_matmul_precision("highest"):
            loss, vjp = jax.vjp(
                lambda a, b: pallas_ce.xla_cross_entropy(a, b, targets),
                x.astype(jnp.float32), w.astype(jnp.float32),
            )
            return (loss, *vjp(g))

    got, text, c, r = compile_and_run(fused, x, w, targets, g)
    compile_s, run_s = compile_s + c, run_s + r
    checks["ce_kernels_in_program"] = all(n in text for n in CE_KERNELS)
    rows = max(N // 4, 1)  # a quarter of the rows' float32 logits at a time
    want = [
        ce_reference(x[i:i + rows], w, targets[i:i + rows], g[i:i + rows])
        for i in range(0, N, rows)
    ]
    want = (
        jnp.concatenate([t[0] for t in want]),
        jnp.concatenate([t[1] for t in want]),
        sum(t[2] for t in want),
    )
    loss_err = float(jnp.abs(got[0] - want[0]).max())
    report["ce_loss_max_abs"] = loss_err
    checks["ce_loss"] = bool(jnp.isfinite(got[0]).all()) and loss_err <= CE_LOSS_TOL
    for name, gg, ww in zip(("dx", "dw"), got[1:], want[1:]):
        e = errors(gg, ww)
        report[f"ce_{name}"] = e
        checks[f"ce_{name}"] = close(e)

    report.update(compile_s=round(compile_s, 1), run_s=round(run_s, 3),
                  checks=checks)
    return report


# ------------------------------------------------------------------ train
def train_loop(config: dict) -> None:
    """Runs in the JaxTrainer worker, which owns every local chip."""
    from functools import partial

    import jax
    from jax.sharding import PartitionSpec as P

    from ray_tpu import parallel, train
    from ray_tpu._private.jax_utils import device_report
    from ray_tpu.models import llama

    shapes: Shapes = config["shapes"]
    cfg = shapes.config(remat=True, attention_impl="flash")
    devices = jax.devices()
    n = len(devices)
    mesh = parallel.make_mesh(devices=devices)  # every chip on fsdp
    # warm-up short enough that eight steps move the loss
    opt = parallel.default_optimizer(1e-4, warmup_steps=4, total_steps=1000)
    specs = llama.param_specs(cfg)

    t0 = time.perf_counter()
    state, state_sh = parallel.create_train_state(
        mesh, jax.random.PRNGKey(SEED),
        lambda r: llama.init_params(r, cfg), opt, specs,
    )
    step = parallel.make_train_step(
        partial(llama.loss_fn, config=cfg), opt, mesh, state_sh
    )
    batches = train.get_dataset_shard("train").iter_batches(
        batch_size=shapes.seqs_per_chip * n,
        device_put=parallel.batch_sharding(mesh),
    )
    batch = next(batches)
    compiled = step.lower(state, batch).compile()
    text = compiled.as_text()
    compile_s = time.perf_counter() - t0

    # every parameter the specs shard: one 1/k shard on each device
    # (k the product of the mesh axes its spec names), none whole
    sharded = whole = 0
    for leaf, spec in zip(
        jax.tree.leaves(state.params),
        jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)),
    ):
        k = 1
        for axes in spec:
            for axis in (axes,) if isinstance(axes, str) else (axes or ()):
                k *= mesh.shape[axis]
        if k == 1:
            continue
        shards = leaf.addressable_shards
        if (
            {s.device for s in shards} == set(devices)
            and all(s.data.size * k == leaf.size for s in shards)
        ):
            sharded += 1
        else:
            whole += 1

    t0 = time.perf_counter()
    losses = []
    while len(losses) < TRAIN_STEPS:
        state, metrics = compiled(state, batch)
        losses.append(float(metrics["loss"]))
        if len(losses) < TRAIN_STEPS:
            batch = next(batches)
    run_s = time.perf_counter() - t0

    stats = [d.memory_stats() or {} for d in devices]
    train.report({
        **device_report(),
        "compile_s": round(compile_s, 1),
        "run_s": round(run_s, 3),
        "losses": [round(x, 4) for x in losses],
        "flash_kernels_in_program": all(n in text for n in FLASH_KERNELS),
        "mesh": {a: s for a, s in mesh.shape.items() if s > 1},
        "params_sharded_1_over_n_on_every_device": sharded,
        "params_not_so": whole,
        "peak_bytes_in_use_per_device": [
            s.get("peak_bytes_in_use") for s in stats
        ],
    })


def train_phase(shapes: Shapes, n_chips: int) -> dict:
    import tempfile

    import numpy as np

    import ray_tpu.data
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    # one seeded global batch, repeated: a block per step
    rng = np.random.default_rng(SEED)
    vocab = shapes.config().vocab_size
    tokens = rng.integers(
        0, vocab, (shapes.seqs_per_chip * max(n_chips, 1), shapes.seq + 1),
        dtype=np.int32,
    )
    dataset = ray_tpu.data.from_numpy([tokens] * TRAIN_STEPS, column="tokens")
    scaling = (
        ScalingConfig(use_tpu=True, tpu_chips_per_worker=n_chips)
        if n_chips
        else ScalingConfig()
    )
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as storage:
        result = JaxTrainer(
            train_loop,
            train_loop_config={"shapes": shapes},
            scaling_config=scaling,
            run_config=RunConfig(name="chip_smoke", storage_path=storage),
            datasets={"train": dataset},
        ).fit()
    # fit() returns the error instead of raising it
    if result.error is not None:
        return {"error": repr(result.error)}
    if not result.metrics:
        return {"error": "the train loop ended without its report"}
    report = dict(result.metrics)
    losses = report.get("losses") or [float("nan")]
    uniform = float(np.log(vocab))
    report["checks"] = {
        # unit-variance logits over V classes cost about ln V + 1/2
        "first_loss_near_ln_vocab": uniform - 0.1 <= losses[0] <= uniform + 1.1,
        "loss_fell": losses[-1] <= losses[0] - shapes.loss_drop,
        "flash_kernels_in_program": bool(report.get("flash_kernels_in_program")),
        "mesh_spans_every_chip": report.get("device_count") == max(n_chips, 1),
        "every_sharded_param_split": (
            report.get("params_not_so") == 0
            and (n_chips <= 1
                 or report.get("params_sharded_1_over_n_on_every_device", 0) > 0)
        ),
    }
    return report


# ------------------------------------------------------------------ serve
def serve_phase(shapes: Shapes, n_chips: int) -> dict:
    import numpy as np

    from ray_tpu import serve
    from ray_tpu.llm import LLMConfig, build_llm_app

    cfg = shapes.config()
    llm_config = LLMConfig(
        model_config=cfg, max_batch_size=8, max_seq_len=shapes.max_seq,
        accelerator_type="TPU" if n_chips else "",
    )
    replicas = max(n_chips, 1)
    app = build_llm_app(llm_config)
    # one replica per chip; enough request threads that a health ping
    # never queues behind the generations
    app = app.deployment.options(
        num_replicas=replicas, max_ongoing_requests=32
    ).bind(*app.args)
    port = _free_port()
    t0 = time.perf_counter()
    handle = serve.run(
        app, route_prefix="/llm", http_options={"port": port}
    ).options(request_timeout_s=900)

    # a dozen seeded prompts, two of them repeats, once per replica
    rng = np.random.default_rng(SEED)
    lo, hi = shapes.prompt_lens
    prompts = [
        rng.integers(0, cfg.vocab_size, int(n)).tolist()
        for n in rng.integers(lo, hi + 1, 10)
    ]
    prompts += [prompts[2], prompts[5]]

    def body(prompt):
        return {"prompt_ids": prompt, "max_tokens": MAX_TOKENS,
                "temperature": 0.0}

    first = handle.remote(body(prompts[0])).result(timeout_s=900)
    warm_s = time.perf_counter() - t0  # replica start-up and compiles

    t0 = time.perf_counter()
    pending = [
        (i, handle.remote(body(p)))
        for _ in range(replicas) for i, p in enumerate(prompts)
    ]
    answers = [(i, r.result(timeout_s=900)["token_ids"]) for i, r in pending]
    run_s = time.perf_counter() - t0

    request = urllib.request.Request(
        f"http://127.0.0.1:{port}/llm",
        data=json.dumps(body(prompts[0])).encode(),
        headers={"Content-Type": "application/json",
                 "X-Request-Timeout-S": "600"},
    )
    with urllib.request.urlopen(request, timeout=600) as response:
        over_http = json.loads(response.read())["token_ids"]

    by_prompt: dict = {}
    for i, tokens in answers:
        by_prompt.setdefault(tuple(prompts[i]), []).append(tokens)
    # each replica's own account of where it ran
    seen: dict = {}
    for _ in range(16 * replicas):
        if len(seen) == replicas:
            break
        stats = handle.engine_stats.remote().result(timeout_s=120)
        seen[stats["pid"]] = stats
    stats = list(seen.values())
    want_platform = "tpu" if n_chips else "cpu"
    report = {
        "platform": ",".join(sorted({s["platform"] for s in stats})),
        "device_kind": ",".join(sorted({s["device_kind"] for s in stats})),
        "device_count": sum(s["device_count"] for s in stats),
        "pid": sorted(seen),
        "replicas": replicas,
        "requests": len(answers) + 2,
        "compile_s": round(warm_s, 1),
        "run_s": round(run_s, 3),
        "peak_active_per_replica": [s["peak_active"] for s in stats],
        # EngineStats, per replica: what the steps did, the share of
        # decode lanes that held a request, host milliseconds a step
        # outside the two waits on the device, and the slowest request's
        # four phases
        "engine_per_replica": [_engine_summary(s["engine"]) for s in stats],
    }
    report["checks"] = {
        "every_request_full_length": all(
            len(t) == MAX_TOKENS for _, t in answers
        ) and len(first["token_ids"]) == MAX_TOKENS,
        "tokens_in_vocabulary": all(
            0 <= t < cfg.vocab_size for _, ts in answers for t in ts
        ),
        "equal_prompts_equal_tokens": all(
            all(t == group[0] for t in group) for group in by_prompt.values()
        ) and first["token_ids"] == by_prompt[tuple(prompts[0])][0],
        "http_equals_handle": over_http == first["token_ids"],
        "every_replica_reported": len(stats) == replicas,
        "one_chip_per_replica": all(
            s["platform"] == want_platform and s["device_count"] == 1
            for s in stats
        ),
        "slots_shared": all(s["peak_active"] > 1 for s in stats),
    }
    serve.shutdown()
    return report


def _engine_summary(engine: dict) -> dict:
    """The fields of ``engine_stats()["engine"]`` an operator reads
    first, from one snapshot (cumulative since the replica started)."""
    from ray_tpu.llm._internal.engine import REQUEST_PHASES

    seconds = {n: p["seconds"] for n, p in engine["phases"].items()}
    steps = max(engine["steps"], 1)
    host_s = (seconds.get("llm.step", 0.0)
              - seconds.get("llm.first_token_sync", 0.0)
              - seconds.get("llm.decode_sync", 0.0))
    slowest = max(engine["requests"], key=lambda r: sum(r[1:5]),
                  default=None)
    return {
        "steps": engine["steps"],
        "tokens_emitted": engine["tokens_emitted"],
        "prefill_chunks": engine["prefill_chunks"],
        "prefill_tokens": engine["prefill_tokens"],
        "decode_calls": engine["decode_calls"],
        "decode_occupancy_pct": round(
            100.0 * engine["decode_lanes_active"]
            / max(engine["decode_lanes_total"], 1), 1),
        "shards_grown": engine["shards_grown"],
        "requests_finished": engine["requests_finished"],
        "requests_refused": engine["requests_refused"],
        "host_ms_per_step": round(1e3 * host_s / steps, 3),
        "slowest_request_phases_ms": slowest and {
            name: round(1e3 * s, 1)
            for name, s in zip(REQUEST_PHASES, slowest[1:5])},
    }


# ----------------------------------------------------------------- driver
def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _cache_entries() -> int:
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def _adopt_orphans() -> None:
    """Have the kernel hand this process the descendants whose parent
    dies first (PR_SET_CHILD_SUBREAPER), so that _stop_descendants can
    see a worker's orphan as well as a worker."""
    ctypes.CDLL(None, use_errno=True).prctl(36, 1, 0, 0, 0)


def _stop_descendants() -> list:
    """Kills and reaps whatever is still below this process, zombies
    included, and returns what it found. After ray_tpu.shutdown() that
    must be nothing."""
    me, found = os.getpid(), []
    for name in os.listdir("/proc"):
        if not name.isdigit():
            continue
        try:
            with open(f"/proc/{name}/stat") as f:
                # "pid (comm) state ppid ..."; comm may hold anything
                state, ppid = f.read().rsplit(")", 1)[1].split()[:2]
            with open(f"/proc/{name}/cmdline") as f:
                cmd = f.read().replace("\0", " ").strip()
        except (OSError, ValueError):
            continue  # gone while we looked
        if int(ppid) == me:
            found.append({"pid": int(name), "state": state, "cmd": cmd[:120]})
    for p in found:
        try:
            os.kill(p["pid"], signal.SIGKILL)
            os.waitpid(p["pid"], 0)
        except OSError:
            pass  # reaped by its Popen in the meantime
    # the killed may have left us orphans of their own
    return found + (_stop_descendants() if found else [])


def _driver_backend_initialised():
    """Whether this process ever created a JAX backend. It imports jax
    (a model config needs jnp.bfloat16) but must never open a device."""
    if "jax" not in sys.modules:
        return False
    try:
        from jax._src import xla_bridge

        return bool(xla_bridge.backends_are_initialized())
    except (ImportError, AttributeError):
        return None  # this jax no longer says


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--rehearse-on-cpu", action="store_true",
        help="toy shapes on the CPU: debugs this script, proves nothing "
        "about the chip",
    )
    args = parser.parse_args()
    rehearsal = args.rehearse_on_cpu

    import ray_tpu

    _adopt_orphans()
    if rehearsal:
        print("REHEARSAL on the CPU at toy shapes: this exercises the "
              "script's control flow and says nothing about the chip.",
              flush=True)
        os.environ["JAX_PLATFORMS"] = "cpu"
        ray_tpu.init(num_tpus=0)
    else:
        ray_tpu.init()
    n_chips = int(ray_tpu.cluster_resources().get("TPU", 0))
    if not rehearsal and n_chips == 0:
        ray_tpu.shutdown()
        _stop_descendants()
        print(
            "chip_smoke: ray_tpu.init() found no TPU chip on this machine "
            "(no /dev/vfio/<n> or /dev/accel<n>); this check runs only on "
            "an accelerator. --rehearse-on-cpu debugs the script itself.",
            file=sys.stderr,
        )
        return 1
    shapes = TOY if rehearsal else CHIP
    want_platform = "cpu" if rehearsal else "tpu"
    cache_before = _cache_entries()

    def give_up():
        print(f"chip_smoke: not done after {DEADLINE_S}s; giving up",
              file=sys.stderr, flush=True)
        try:
            ray_tpu.shutdown()
        finally:
            _stop_descendants()
            os._exit(1)

    watchdog = threading.Timer(DEADLINE_S, give_up)
    watchdog.daemon = True
    watchdog.start()

    # max_calls=1: the task's worker exits when it returns, and the
    # chip it held goes back to the scheduler for the next phase
    kernels = ray_tpu.remote(num_tpus=min(n_chips, 1), max_calls=1)(kernels_phase)
    phases = (
        ("kernels", lambda: ray_tpu.get(kernels.remote(shapes), timeout=900)),
        ("train", lambda: train_phase(shapes, n_chips)),
        ("serve", lambda: serve_phase(shapes, n_chips)),
    )
    failed = []
    reports = {}
    try:
        for name, run in phases:
            t0 = time.perf_counter()
            try:
                report = run()
            except Exception as e:  # a phase's failure is the result
                report = {"error": f"{type(e).__name__}: {e}"[:2000]}
            report = {"phase": name, **report,
                      "wall_s": round(time.perf_counter() - t0, 1)}
            checks = report.get("checks") or {}
            checks["on_" + want_platform] = (
                report.get("platform") == want_platform
            )
            if "interpreted" in report and not rehearsal:
                checks["kernels_compiled_not_interpreted"] = (
                    report["interpreted"] is False
                )
            report["checks"] = checks
            report["ok"] = "error" not in report and all(checks.values())
            if not report["ok"]:
                failed.append(name)
            reports[name] = report
            print(json.dumps(report), flush=True)
    finally:
        ray_tpu.shutdown()
        left_behind = _stop_descendants()
        watchdog.cancel()

    pids = [reports[p].get("pid") for p in reports]
    flat = [x for p in pids for x in (p if isinstance(p, list) else [p])]
    distinct = len(set(flat)) == len(flat) and os.getpid() not in flat
    summary = {
        "phase": "driver",
        "pid": os.getpid(),
        "backend_initialised": _driver_backend_initialised(),
        "chip_holders_distinct_from_each_other_and_driver": distinct,
        "compile_cache": os.environ.get("JAX_COMPILATION_CACHE_DIR"),
        "compile_cache_entries": [cache_before, _cache_entries()],
        "left_behind_by_shutdown": left_behind,
    }
    summary["ok"] = (
        summary["backend_initialised"] is not True
        and distinct
        and summary["compile_cache_entries"][1] > 0
        and not left_behind
    )
    if not summary["ok"]:
        failed.append("driver")
    print(json.dumps(summary), flush=True)

    if failed:
        print(json.dumps({"ok": False, "failed": failed}), flush=True)
        return 1
    train = reports["train"]
    final = {"ok": True, "device": {
        "platform": train["platform"], "kind": train["device_kind"],
        "count": train["device_count"],
    }}
    if rehearsal:
        final["rehearsal"] = True
    print(json.dumps(final), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
