"""LLM serving: continuous-batching deployment over ray_tpu.serve.

Parity: python/ray/llm/_internal/serve/deployments/llm/ (VLLMService +
build_openai_app) re-designed TPU-native — the engine is the in-tree
Llama with an XLA KV cache (llm/_internal/engine.py), not a wrapped
vLLM; requests stream tokens through the serve streaming-response path
(handle.options(stream=True) over num_returns="streaming").

What a request spends outside the engine is recorded beside the
engine's own stamps (``GenRequest.routed`` / ``received`` /
``first_yielded``; ``engine_stats()["loop_phases"]``), always on, at the
cost of two ``time.monotonic()`` a token and a few floats a request. In
a request's order: ``serve.ingress`` (the handle's route entry to the
replica's method), ``llm.accept`` (to the pending queue),
``llm.first_token_handoff`` (the loop's read of the first token to the
request's thread holding it), then for every token
``llm.token_handoff`` (the loop having the step's tokens to the
thread's ``q.get`` returning), which is ``llm.token_backlog`` (the
token lay in the queue before the thread came back to ask: the thread
was inside an earlier token's ``yield``) plus ``llm.token_wake`` (the
thread was waiting: the loop's put and the interpreter's switch), and
``llm.token_yield`` (``yield tok`` to the generator being resumed: the
worker's encode, its STREAM_YIELD send, a bounded stream's wait for
credit). Behind the ``yield`` the stretches are the runtime's
(``DeploymentHandle.stream_stats()``, the hub's
``ray_tpu_stream_*_total`` counters; ``util/tracing.py`` lists the whole
chain).

HTTP: `serve.run(build_llm_app(cfg))` exposes POST /<name> with JSON
{"prompt_ids": [...], "max_tokens": N, "temperature": t, "stream": bool}
via the existing serve proxy.
"""

from __future__ import annotations

import itertools
import queue
import threading
import time
from typing import Any, Dict, List, Optional

from ray_tpu.util import tracing

from .config import LLMConfig


class LLMServer:
    """Deployment class: one engine + a background continuous-batching
    loop; concurrent callers enqueue and stream tokens out."""

    def __init__(self, llm_config: LLMConfig):
        self.config = llm_config
        params = llm_config.load_params()
        from ._internal.engine import LlamaEngine

        self._base_params = params
        # the engine builds what the configuration says: its model
        # module's programs and cache
        self._model_config = llm_config.resolved_model_config()
        self.engine = LlamaEngine(
            self._model_config,
            params,
            max_batch=llm_config.max_batch_size,
            max_seq=llm_config.max_seq_len,
            **llm_config.engine_kwargs,
        )
        self.engine.warm_up()  # no request pays a compile
        # LoRA multiplexing: adapter id -> folded-weights engine, LRU-
        # capped (never evicting active engines — which is why this is
        # a hand-rolled cache rather than @serve.multiplexed); loaded
        # ids ride the serve multiplex registry so the router prefers
        # replicas already holding an adapter
        from collections import OrderedDict

        self._engines: "OrderedDict[str, LlamaEngine]" = OrderedDict()
        self._loading: set = set()  # adapter ids mid-cold-load (cap slots)
        self._engines[""] = self.engine
        self._engines_lock = threading.Lock()
        self._reporter = None
        if llm_config.lora_config:
            from ray_tpu.serve.multiplex import register_model_reporter

            self._reporter = register_model_reporter(self._loaded_adapters)
        self._pending: "queue.Queue" = queue.Queue()
        self._id_counter = itertools.count()
        self._token_queues: Dict[str, "queue.Queue"] = {}
        self._lock = threading.Lock()
        # seconds and counts of the batching loop's own parts (llm.admit,
        # llm.emit, llm.idle_sleep); only the loop's thread adds to them
        self._loop_phases = tracing.PhaseStats()
        # what the requests' own threads fold in as each ends, under
        # self._lock: serve.ingress, llm.accept, llm.first_token_handoff
        # (a count a request), llm.token_handoff and its two parts
        # llm.token_backlog and llm.token_wake, llm.token_yield (a count
        # a token)
        self._request_phases = tracing.PhaseStats()
        self._running = True
        self._loop_thread = threading.Thread(
            target=self._batching_loop, daemon=True, name="llm-batching"
        )
        self._loop_thread.start()

    # -- LoRA engines --------------------------------------------------
    def _loaded_adapters(self):
        with self._engines_lock:
            return [aid for aid in self._engines if aid]

    def shutdown(self) -> None:
        """Stop the batching loop and drop the multiplex registration.
        Must be called explicitly for in-process servers: the batching
        thread and the multiplex registry hold strong refs, so __del__
        would never fire (Serve replicas die with their actor process,
        which achieves the same)."""
        self._running = False
        if self._reporter is not None:
            from ray_tpu.serve.multiplex import unregister_model_reporter

            unregister_model_reporter(self._reporter)
            self._reporter = None

    def _engine_for(self, adapter_id: str):
        """Engine serving this adapter, loading + folding on first use
        (LRU-capped per lora_config.max_adapters_per_replica).

        Callers invoke this at SUBMISSION time (their own thread) so a
        cold load — disk read + fold + KV-cache alloc + the engine's
        ``warm_up()`` (every program compiled or loaded, and run once) —
        never stalls the batching loop's token emission for other
        requests; the loop only re-resolves on the rare
        submitted-then-evicted race."""
        with self._engines_lock:
            eng = self._engines.get(adapter_id)
            if eng is not None:
                self._engines.move_to_end(adapter_id)
                return eng
        lora = self.config.lora_config
        if not lora:
            raise ValueError(
                f"request for adapter {adapter_id!r} but no lora_config"
            )
        import os

        if (
            not adapter_id
            or "/" in adapter_id
            or "\\" in adapter_id
            or ".." in adapter_id
        ):
            # the id comes from request bodies: it must never be able to
            # escape dynamic_lora_loading_path
            raise ValueError(f"invalid adapter id {adapter_id!r}")

        cap = int(lora.get("max_adapters_per_replica", 4))
        with self._engines_lock:
            # HARD cap: when every loaded adapter is mid-generation and
            # the cap is reached, refuse — an unbounded engine pile-up
            # (full KV cache each) OOMs the replica. In-flight loads
            # count via the _loading placeholder set, closing the
            # check-then-act window (the load itself runs unlocked for
            # seconds).
            busy = [
                aid for aid in self._engines
                if aid and self._engines[aid].num_active()
            ]
            if len(busy) + len(self._loading) >= cap:
                raise RuntimeError(
                    f"all {cap} adapter slots are busy; retry later "
                    "(max_adapters_per_replica)"
                )
            self._loading.add(adapter_id)

        try:
            from ._internal.engine import LlamaEngine
            from .lora import apply_lora, load_lora_adapter

            base = lora["dynamic_lora_loading_path"]
            path = (
                base.format(adapter_id)
                if "{}" in base
                else os.path.join(base, adapter_id + ".npz")
            )
            folded = apply_lora(
                self._base_params,
                load_lora_adapter(path),
                scale=float(lora.get("scale", 1.0)),
            )
            eng = LlamaEngine(
                self._model_config,
                folded,
                max_batch=self.config.max_batch_size,
                max_seq=self.config.max_seq_len,
                **self.config.engine_kwargs,
            )
            eng.warm_up()
        finally:
            with self._engines_lock:
                self._loading.discard(adapter_id)
        with self._engines_lock:
            existing = self._engines.get(adapter_id)
            if existing is not None:  # lost a racing load of the same id
                return existing
            self._engines[adapter_id] = eng
            # LRU-evict idle adapters past the cap — never the base "",
            # never an engine mid-generation, never the one just loaded
            evictable = [
                aid for aid in self._engines
                if aid and aid != adapter_id
                and not self._engines[aid].num_active()
            ]
            while len(self._engines) - 1 > cap and evictable:
                del self._engines[evictable.pop(0)]
        return eng

    # -- continuous batching loop -------------------------------------
    def _batching_loop(self):
        while self._running:
            admitted = self._admit_pending()
            stepped = False
            with self._engines_lock:
                live_engines = list(self._engines.values())
            for eng in live_engines:
                if not eng.num_active():
                    continue
                stepped = True
                try:
                    emitted = eng.step()
                except Exception as e:
                    # engine fault: fail every in-flight request, keep serving
                    for req in eng.abort_all():
                        q = self._token_queues.get(req.request_id)
                        if q is not None:
                            q.put(("error", e, 0.0))
                    continue
                # when this thread had the step's tokens: each rides the
                # queue with it, and its request's thread reads the
                # hand-off's length off it (llm.token_handoff)
                t_read = time.monotonic()
                # the device is running the decodes step() dispatched
                # while this thread emits and, next round, admits
                with tracing.phase("llm.emit", self._loop_phases):
                    done = {}
                    for req, tok in emitted:
                        q = self._token_queues.get(req.request_id)
                        if q is not None:
                            q.put(("token", tok, t_read))
                            if req.done:
                                done[req.request_id] = (q, req)
                    # behind every token of the step, the request's last too
                    for q, req in done.values():
                        q.put(("done", req, t_read))
            if not stepped and not admitted:
                with tracing.phase("llm.idle_sleep", self._loop_phases):
                    time.sleep(0.005)

    def _admit_pending(self) -> bool:
        """Admit as many pending requests as their engines have slots."""
        if self._pending.empty():
            return False
        admitted = False
        requeue = []
        with tracing.phase("llm.admit", self._loop_phases):
            while True:
                try:
                    req = self._pending.get_nowait()
                except queue.Empty:
                    break
                q = self._token_queues.get(req.request_id)
                try:
                    eng = self._engine_for(req.adapter_id)
                except Exception as e:
                    if q is not None:
                        q.put(("error", e, 0.0))
                    continue
                if not eng.has_capacity():
                    requeue.append(req)
                    continue
                try:
                    ok = eng.add_request(req)
                except Exception as e:
                    # a bad request (e.g. prompt >= max_seq) must fail
                    # its own caller, never the batching thread
                    if q is not None:
                        q.put(("error", e, 0.0))
                    continue
                if ok is False:
                    # no slot after all (has_capacity raced a concurrent
                    # admit): retry next loop instead of dropping the
                    # request on the floor
                    requeue.append(req)
                    continue
                admitted = True
                # the first token arrives from step() once the chunked
                # prefill completes — nothing to emit at admission
            for req in requeue:
                self._pending.put(req)
        return admitted

    # -- request entrypoints ------------------------------------------
    def generate_stream(
        self,
        prompt_ids: List[int],
        max_tokens: int = 64,
        temperature: float = 0.0,
        eos_id: Optional[int] = None,
        adapter_id: Optional[str] = None,
    ):
        """Generator: yields token ids as the engine produces them
        (invoked through serve's streaming path)."""
        from ray_tpu.serve._private.observability import request_stamps

        from ._internal.engine import GenRequest

        rid = f"req{next(self._id_counter)}"
        # the replica's work on this request before the pending queue,
        # once a request and in its own thread
        with tracing.phase("serve.accept", request_id=rid):
            if adapter_id is None:
                # serve routing: handle.options(multiplexed_model_id=...)
                from ray_tpu.serve import get_multiplexed_model_id

                adapter_id = get_multiplexed_model_id()
            if adapter_id:
                # cold-load in THIS thread (see _engine_for docstring):
                # load errors also surface here, at submission, with a
                # stack
                self._engine_for(adapter_id)
            q: "queue.Queue" = queue.Queue()
            with self._lock:
                self._token_queues[rid] = q
            # set by serve.execute for a sampled request; None otherwise
            trace_ctx = tracing.current_context()
            req = GenRequest(
                request_id=rid,
                prompt_ids=list(prompt_ids),
                max_tokens=max_tokens,
                temperature=temperature,
                eos_id=eos_id,
                adapter_id=adapter_id or "",
            )
            # the handle's and the replica's stamps; a caller no handle
            # routed (a test, a server used in-process) has none, and
            # both count as submitted
            routed, received = request_stamps() or (0.0, 0.0)
            req.submitted = time.monotonic()
            req.routed = routed or req.submitted
            req.received = received or req.submitted
            self._pending.put(req)
        try:
            # when this thread last came to the queue to wait
            t_wait = time.monotonic()
            # the wait for the first token, to just before its yield
            with tracing.phase("llm.first_yield", request_id=rid):
                item = q.get(timeout=120)
            while True:
                kind, tok, t_read = item
                if kind == "done":
                    if trace_ctx is not None:
                        self._emit_request_span(trace_ctx, tok)
                    return
                if kind == "error":
                    raise tok
                now = time.monotonic()
                req.handoff_s += now - t_read
                req.handoff_n += 1
                if t_wait > t_read:
                    # the token was there before this thread asked: it
                    # lay in the queue that long (the rest of its
                    # hand-off is this thread's wake)
                    req.backlog_s += t_wait - t_read
                req.first_yielded = req.first_yielded or now
                yield tok
                # resumed: the runtime has encoded and sent the token
                # and waited for credit where the stream is bounded
                t_wait = time.monotonic()
                req.yield_s += t_wait - now
                req.yield_n += 1
                item = q.get(timeout=120)
        finally:
            with self._lock:
                self._token_queues.pop(rid, None)
                self._fold_request(req)

    def _fold_request(self, req) -> None:
        """A request's time outside the engine into ``_request_phases``,
        by its own thread as it ends, under ``self._lock``."""
        add = self._request_phases.add
        add("serve.ingress", req.received - req.routed)
        add("llm.accept", req.submitted - req.received)
        if req.first_yielded:
            add("llm.first_token_handoff",
                req.first_yielded - req.first_token)
            add("llm.token_handoff", req.handoff_s, req.handoff_n)
            # the hand-off in two: in the queue before the thread asked,
            # and the thread's wake once it was waiting
            add("llm.token_backlog", req.backlog_s, req.handoff_n)
            add("llm.token_wake", req.handoff_s - req.backlog_s,
                req.handoff_n)
            add("llm.token_yield", req.yield_s, req.yield_n)

    @staticmethod
    def _emit_request_span(trace_ctx, req) -> None:
        """One ``llm.request`` span record for a finished request that
        arrived under a sampled trace: the handle's route entry (the
        replica's, where no handle stamped it) to its last token, with
        its six phases, the first token's hand-off to the request's
        thread and the chunk count as attributes."""
        tracing._emit(tracing.make_runtime_record(
            "llm.request", "llm.request", trace_ctx[0], trace_ctx[1],
            req.routed, req.finished,
            attrs={"request_id": req.request_id,
                   "prefill_chunks": req.prefill_chunks,
                   "first_token_handoff_s": round(
                       req.first_yielded - req.first_token, 6),
                   **{f"{k}_s": round(v, 6)
                      for k, v in req.phases().items()}},
        ))

    def generate(self, prompt_ids, max_tokens=64, temperature=0.0,
                 eos_id=None, adapter_id=None) -> List[int]:
        return list(
            self.generate_stream(
                prompt_ids, max_tokens, temperature, eos_id, adapter_id
            )
        )

    def __call__(self, request: Dict[str, Any]):
        """Entrypoint for both direct handle calls ({"prompt_ids": ...})
        and the serve HTTP proxy (request dict with a raw JSON body)."""
        if "prompt_ids" not in request and request.get("body"):
            import json

            request = json.loads(request["body"])
        prompt_ids = request.get("prompt_ids")
        if prompt_ids is None:
            raise ValueError("request must contain 'prompt_ids'")
        # "model" in the body (openai-style) beats routing context
        model = self._resolve_adapter(request)
        toks = self.generate(
            prompt_ids,
            max_tokens=int(request.get("max_tokens", 64)),
            temperature=float(request.get("temperature", 0.0)),
            eos_id=request.get("eos_id"),
            adapter_id=model,
        )
        return {"token_ids": toks, "num_generated": len(toks)}

    def _resolve_adapter(self, request: Dict[str, Any]) -> Optional[str]:
        """'model' in a request body -> adapter id: the base model's own
        name (model_id) or "" routes to the base engine; anything else
        is a LoRA adapter id (reference ray.llm routing semantics).
        None = no field, fall back to the serve routing context."""
        model = request.get("model")
        if model is not None and model in ("", self.config.model_id):
            return ""
        return model

    def engine_stats(self) -> Dict[str, Any]:
        from ray_tpu._private.jax_utils import device_report

        with self._lock:  # the requests' threads fold under it
            request_phases = self._request_phases.snapshot()
        return {
            "active": self.engine.num_active(),
            "peak_active": self.engine.peak_active,
            "free_slots": sum(
                len(s.free_slots) for s in self.engine.shards
            ),
            "max_batch": self.engine.max_batch,
            # the rows a prefill call carries at the most, as the engine
            # derived them or was given them
            "prefill_chunk": self.engine.prefill_chunk,
            "shards": len(self.engine.shards),
            # cumulative counters, seconds per part of step(), and the
            # finished requests' phases (EngineStats.snapshot); a reader
            # takes two of these and subtracts
            "engine": self.engine.stats.snapshot(),
            # the batching loop's own parts, and the requests' time
            # outside the engine (the names do not collide)
            "loop_phases": {**self._loop_phases.snapshot(),
                            **request_phases},
            **device_report(),
        }


def build_llm_app(llm_config: LLMConfig, name: str = "llm", server_cls=None):
    """Bound deployment for `serve.run` (reference: build_openai_app).
    Sizes actor resources from the TP x PP placement bundles."""
    from ray_tpu import serve

    bundles, strategy = llm_config.placement_bundles()
    # single-bundle (pp=1) deployments pin the whole gang's chips on the
    # replica actor; multi-bundle pp is reserved via a placement group by
    # the replica itself when it spins stage actors (future work: true
    # cross-host pp stages)
    num_tpus = bundles[0].get("TPU", 0) if llm_config.accelerator_type == "TPU" else 0
    deployment = serve.deployment(
        server_cls or _LLMServerWrapper,
        name=name,
        ray_actor_options={"num_tpus": num_tpus} if num_tpus else None,
    )
    return deployment.bind(llm_config)


class _LLMServerWrapper(LLMServer):
    """Deployment wrapper (serve.deployment needs a fresh class so user
    code can also subclass LLMServer directly)."""


class OpenAIServer(LLMServer):
    """OpenAI-style completions surface (reference: build_openai_app's
    router deployments). Accepts completion bodies:

        {"model": "<model_id or lora adapter id>",
         "prompt": [token ids] (or "prompt_ids"),
         "max_tokens": N, "temperature": t}

    and answers {"object": "text_completion", "model": ...,
    "choices": [{"token_ids": [...], "index": 0,
    "finish_reason": "length"|"stop"}], "usage": {...}}. Token-id in/out:
    tokenization happens client-side (there is no tokenizer dependency
    in-tree)."""

    def __call__(self, request: Dict[str, Any]):
        import json

        if "prompt" not in request and "prompt_ids" not in request and request.get("body"):
            request = json.loads(request["body"])
        prompt_ids = request.get("prompt_ids") or request.get("prompt")
        if not isinstance(prompt_ids, list):
            raise ValueError(
                "completion request needs 'prompt' (token-id list)"
            )
        adapter = self._resolve_adapter(request)
        max_tokens = int(request.get("max_tokens", 64))
        eos_id = request.get("eos_id")
        toks = self.generate(
            prompt_ids,
            max_tokens=max_tokens,
            temperature=float(request.get("temperature", 0.0)),
            eos_id=eos_id,
            adapter_id=adapter,
        )
        # "stop" ONLY on an eos match; anything else — max_tokens hit or
        # the engine's max_seq context truncation — is "length"
        finish = (
            "stop"
            if eos_id is not None and toks and toks[-1] == eos_id
            else "length"
        )
        return {
            "object": "text_completion",
            "model": adapter or self.config.model_id,
            "choices": [
                {"index": 0, "token_ids": toks, "finish_reason": finish}
            ],
            "usage": {
                "prompt_tokens": len(prompt_ids),
                "completion_tokens": len(toks),
                "total_tokens": len(prompt_ids) + len(toks),
            },
        }


def build_openai_app(llm_config: LLMConfig, name: str = "v1-completions"):
    """Bound OpenAI-compatible completions app (reference:
    ray.llm build_openai_app); serve with
    ``serve.run(app, route_prefix="/v1/completions")``."""
    return build_llm_app(llm_config, name=name, server_cls=OpenAIServer)
