"""Headline benchmark: Llama training throughput on the local chip(s).

Runs only on an accelerator: without one it exits non-zero with the
reason, and it has no other shapes to fall back to. Prints ONE JSON
line:
  {"metric": ..., "value": N, "unit": ..., "platform": ...,
   "device_kind": ..., "device_count": N, "vs_baseline": N}

The reference publishes no LLM-training numbers (BASELINE.md: north-star
targets "to be established by our harness"), so ``vs_baseline`` is
hardware-normalized: measured MFU divided by 0.50 — the MFU an
A100-class baseline (the north star's comparison hardware) typically
sustains on dense decoder training. vs_baseline >= 1.0 means we extract
at least as much of the silicon as the reference stack would.

Model: ~1.1B-param Llama (TinyLlama shape), bf16 params, remat on,
seq 2048 — big enough that MXU utilization is meaningful on one chip,
small enough to fit one v5e's 16 GiB HBM with Adam state.
"""

from __future__ import annotations

import dataclasses
import json
import sys
import time
from functools import partial

# bf16 peak TFLOP/s of one chip, keyed by the ``device_kind`` jax
# reports. A kind that is not here is an error, not a default.
#   "TPU v5 lite": 197 — Google Cloud documentation, "TPU v5e"; the
#   string is what jax.devices()[0].device_kind says on a v5e (chip
#   run, 2026-09-26).
PEAK_BF16_TFLOPS = {"TPU v5 lite": 197.0}


def main() -> None:
    from ray_tpu._private.jax_utils import ensure_compilation_cache_dir

    ensure_compilation_cache_dir()
    import jax
    import jax.numpy as jnp

    from ray_tpu import parallel
    from ray_tpu.models import llama

    device = jax.devices()[0]
    if device.platform == "cpu":
        sys.exit(
            "bench.py measures an accelerator and jax found only the CPU "
            f"({jax.devices()}); a CPU run has no place under this metric"
        )
    if device.device_kind not in PEAK_BF16_TFLOPS:
        raise KeyError(
            f"no peak FLOP/s recorded for device_kind {device.device_kind!r}; "
            "add it to PEAK_BF16_TFLOPS with its source"
        )
    peak = PEAK_BF16_TFLOPS[device.device_kind]

    cfg = dataclasses.replace(
        llama.LLAMA_BENCH, param_dtype=jnp.bfloat16, remat=True,
        attention_impl="flash",  # the Pallas kernel (ops/pallas_attention)
    )
    n_chips = len(jax.devices())
    batch, seq, steps = 8, 2048, 10

    mesh = parallel.make_mesh(devices=jax.devices())
    opt = parallel.default_optimizer(1e-4, warmup_steps=10, total_steps=1000)
    state, state_sh = parallel.create_train_state(
        mesh, jax.random.PRNGKey(0),
        lambda r: llama.init_params(r, cfg), opt, llama.param_specs(cfg),
    )
    step = parallel.make_train_step(
        partial(llama.loss_fn, config=cfg), opt, mesh, state_sh
    )
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (batch, seq + 1), 0, cfg.vocab_size,
        dtype=jnp.int32,
    )
    batch_dict = {"tokens": tokens}

    # Warmup / compile.
    state, metrics = step(state, batch_dict)
    jax.block_until_ready(metrics["loss"])

    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step(state, batch_dict)
    jax.block_until_ready(metrics["loss"])
    dt = time.perf_counter() - t0

    tokens_per_sec = batch * seq * steps / dt
    tps_chip = tokens_per_sec / n_chips

    flops_tok = llama.flops_per_token(cfg, seq)
    achieved_tflops = tokens_per_sec * flops_tok / n_chips / 1e12
    mfu = achieved_tflops / peak

    print(json.dumps({
        "metric": "llama1b_train_tokens_per_sec_per_chip",
        "value": round(tps_chip, 1),
        "unit": "tokens/s/chip",
        # consumers comparing rows must check the device before ratioing
        "platform": device.platform,
        "device_kind": device.device_kind,
        "device_count": n_chips,
        "vs_baseline": round(mfu / 0.50, 3),
        "detail": {
            "model_params": llama.param_count(cfg),
            "batch": batch, "seq": seq, "steps": steps,
            "achieved_tflops_per_chip": round(achieved_tflops, 1),
            "peak_tflops_per_chip": peak,
            "mfu": round(mfu, 3),
            "loss": round(float(metrics["loss"]), 4),
        },
    }))


if __name__ == "__main__":
    main()
