"""The benchmark's own ``LLMServer``: the system under test with step
counters, a trace taken in the replica (the process that holds the
chip), and the float32 comparison run through the engine's own
programs. ``build_llm_app(cfg, server_cls=BenchLLMServer)`` deploys it.
"""

from __future__ import annotations

import dataclasses
import time

from ray_tpu.llm import LLMConfig
from ray_tpu.llm.serve import LLMServer

from . import holder, spec, traffic


@dataclasses.dataclass
class SeededLLMConfig(LLMConfig):
    """Weights from ``--seed`` in one jitted program, in the type they
    are served in (``LLMConfig.load_params`` fixes PRNGKey(0))."""

    seed: int = 0
    rehearse: bool = False

    def load_params(self):
        from functools import partial

        import jax

        from ray_tpu.models import llama

        init = jax.jit(partial(llama.init_params, config=self.model_config))
        return init(spec.prng_key(self.seed))


def _new_counters() -> dict:
    return {"engine_steps": 0, "engine_tokens": 0, "engine_step_s": 0.0,
            "prefill_s": 0.0, "engine_step_ms": [], "prefill_chunks": 0}


class BenchLLMServer(LLMServer):
    def __init__(self, llm_config: SeededLLMConfig):
        self._compiles = holder.CompileCounter()
        self._info = holder.device_info(llm_config.rehearse)
        super().__init__(llm_config)
        self._c = _new_counters()
        self._compiles_at_window = 0
        self._tracer = None
        self._instrument(self.engine)

    def _instrument(self, eng) -> None:
        """Host clock and a host span around ``engine.step()`` and its
        ``_pump_prefill``: instance attributes, so the engine's own
        ``self._pump_prefill(...)`` finds them."""
        import jax

        inner_step, inner_prefill = eng.step, eng._pump_prefill

        def step():
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.engine_step"):
                out = inner_step()
            dt = time.perf_counter() - t0
            c = self._c
            c["engine_steps"] += 1
            c["engine_tokens"] += len(out)
            c["engine_step_s"] += dt
            c["engine_step_ms"].append(1e3 * dt)
            return out

        def pump_prefill(shard, out):
            if not shard.prefilling:
                return inner_prefill(shard, out)
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.prefill_chunk"):
                inner_prefill(shard, out)
            self._c["prefill_s"] += time.perf_counter() - t0
            self._c["prefill_chunks"] += 1

        eng.step, eng._pump_prefill = step, pump_prefill

    # -- set-up ---------------------------------------------------------
    def warm_up(self, prompt_lens, max_tokens: int = 3) -> dict:
        """Runs the decode program and every prefill bucket these prompt
        lengths reach, through the normal request path."""
        for n in prompt_lens:
            self.generate([1 + i % 7 for i in range(n)], max_tokens=max_tokens)
        return dict(self._info)

    def reference_check(self, seed: int, hp: dict, length: int,
                        decode_steps: int) -> dict:
        """Prefill ``length`` seeded tokens, decode ``decode_steps``
        greedy tokens, then prefill one more token, all through the
        engine's own jitted programs into slot 0 of its first cache
        shard, and compare with the float32 reference's full forward
        over the same tokens. The engine must be idle.

        The decode program returns tokens, not logits, so a decode step
        is judged twice: by how far the token it chose lies under the
        reference's largest logit, and through the cache rows it wrote,
        which the final one-token prefill attends to.
        """
        import numpy as np

        from . import reference

        eng = self.engine
        seq = traffic.probe_sequence(seed, length, hp["vocab_size"])
        onehot = np.zeros(eng.max_batch, np.float32)
        onehot[0] = 1.0
        t0 = time.perf_counter()

        def prefill(tokens, pos):
            chunk = len(tokens)
            bucket = next(b for b in eng.buckets if b >= chunk)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :chunk] = tokens
            shard = eng.shards[0]
            logits, shard.cache = eng._prefill(
                eng.params, shard.cache, padded, onehot,
                np.asarray([pos], np.int32), chunk, bucket=bucket)
            return np.asarray(logits, np.float32)

        with eng._lock:
            if eng.num_active():
                raise RuntimeError("reference_check needs an idle engine")
            for pos in range(0, length, eng.prefill_chunk):
                got_prefill = prefill(seq[pos:pos + eng.prefill_chunk], pos)
            chosen = [int(got_prefill.argmax())]
            lens = np.full(eng.max_batch, eng.max_seq - 1, np.int32)
            temps = np.zeros(eng.max_batch, np.float32)
            for i in range(decode_steps):
                last = np.zeros(eng.max_batch, np.int32)
                last[0], lens[0] = chosen[-1], length + i
                shard = eng.shards[0]
                toks, shard.cache, eng._rng = eng._decode(
                    eng.params, shard.cache, last, lens, temps, eng._rng)
                chosen.append(int(np.asarray(toks)[0]))
            got_after = prefill(chosen[-1:], length + decode_steps)
        engine_s = time.perf_counter() - t0

        full = np.concatenate([seq, np.asarray(chosen, np.int32)])
        want = np.asarray(reference.logits(
            eng.params, full, theta=hp["rope_theta"], eps=hp["rms_norm_eps"],
            last=decode_steps + 2))

        def rel_rms(got, ref):
            return float(np.sqrt(np.mean((got - ref) ** 2))
                         / np.sqrt(np.mean(ref ** 2)))

        return {
            "prefill_rel_rms": rel_rms(got_prefill, want[0]),
            "after_decode_rel_rms": rel_rms(got_after, want[-1]),
            # how far under the reference's best logit each chosen token is
            "decode_choice_gap": max(
                float(want[i].max() - want[i][chosen[i]])
                for i in range(decode_steps + 1)),
            "logit_rms": float(np.sqrt(np.mean(want ** 2))),
            "finite": bool(np.isfinite(got_prefill).all()
                           and np.isfinite(got_after).all()),
            "engine_s": engine_s,
            "total_s": time.perf_counter() - t0,
        }

    # -- the window -----------------------------------------------------
    def begin_window(self) -> None:
        self._c = _new_counters()
        self._compiles_at_window = self._compiles.count

    def end_window(self) -> dict:
        c = self._c
        return {
            "samples": dict(c),
            "compiled_in_window": self._compiles.count - self._compiles_at_window,
            "device": {**self._info,
                       "memory_peak_bytes": holder.memory_peak_bytes()},
            "shards": len(self.engine.shards),
            "peak_active": self.engine.peak_active,
        }

    # -- the traced part --------------------------------------------------
    def trace_start(self) -> None:
        self._tracer = holder.Tracer()
        self._tracer.start()

    def trace_stop(self) -> float:
        self._tracer.stop()
        return self._tracer.window_s

    def trace_report(self, per_layer: dict, cell: dict, samples: dict) -> dict:
        """Reduces the trace here, in the process that took it, and
        returns numbers: the traced device block, the breakdown, and the
        cell's per-layer metrics through their readers."""
        trace = self._tracer.reduce()
        self._tracer = None
        ctx = {"cell": cell, "chips": self._info["count"], "samples": samples,
               "trace": trace,
               "peak": spec.peak_for(self._info["kind"], self.config.rehearse)}
        return {
            "device": holder.traced_device_block(trace),
            "breakdown": holder.trace_reduce.breakdown(trace),
            "per_layer": spec.evaluate(per_layer, ctx),
        }
