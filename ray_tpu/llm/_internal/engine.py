"""LlamaEngine: TPU-native generation with continuous batching.

The role vLLM plays for the reference's ray.llm
(reference: python/ray/llm/_internal/serve/deployments/llm/vllm/) —
re-designed for XLA instead of wrapped:

- Slot-based continuous batching: cache SHARDS of ``max_batch`` slots;
  a decode call advances one shard's active slots in one jitted
  (B, 1) program (static shapes; no recompiles as requests come and
  go). When all slots are busy the engine GROWS by allocating another
  shard — same compiled programs, more concurrent sequences — up to
  ``max_slots``. Where a step finds lanes to decode in TWO shards it
  dispatches ONE call over the pair (``_decode_pair``; three or four
  live shards go two to a call, the odd one alone): the same ``decode``
  program traced with the two caches, both donated, and the lanes of
  both as ``2 x max_batch`` rows, at the top read window. Embedding, projections, feed-forward,
  head and sampling run once over all the rows, so a step reads every
  weight once and not once a shard; what touches a cache runs once a
  shard on that shard's own buffer, in place (``models/decoder.py``
  ``Call.by_shard``). An idle or mid-prefill lane of either shard rides
  the call as it rides its own shard's: at the idle position. The
  choice follows what the step observes, the shards with a lane to
  decode, and no option or family name; a step with one such shard runs
  ``_decode`` and the program it always ran. An engine that can never
  hold a second shard (``max_slots == max_batch``) warms no such
  variant.
- CHUNKED prefill: prompts enter the cache ``prefill_chunk`` tokens per
  engine step, interleaved with decode — a long prompt cannot stall
  the decode of already-running sequences (vLLM's chunked-prefill
  scheduler, reference llm/_internal/batch/stages/vllm_engine_stage.py
  wraps the same idea). The chunk is sized from the chip and the
  model, not set. Every call pays some things once whatever its rows,
  so a chunk wants rows enough to pay for them. The rule taken is the
  roofline's ridge: the rows whose own work takes as long as what the
  call pays once, to the nearest power of two
  (``derived_prefill_chunk``). What a call pays once is first the read
  of its weights, and a row's work is its matmuls over them: the rows
  at which a matmul takes as long as reading its weight are the chip's
  FLOPs per HBM byte, times the weights' bytes per parameter over 2
  (240 rows of bf16 on a v5e). They are counted for the weights that
  hold most of a call's bytes, and a model's module says in
  ``chunk_terms`` what the configuration adds to that:
  - *the share of a call's rows that multiply such a weight.* In
    ``models/llama.py`` every row meets every weight (the module says
    nothing): 240 -> 256 rows on a v5e. In ``models/window_moe.py`` the
    experts are 95 % of a layer's bytes and a row meets
    ``experts_per_token`` of ``n_experts`` of them: for 8 of 64 an
    expert sees an eighth of a call's rows, so 240 x 8 = 1920 -> 2048
    rows. Not the call's FLOPs over its bytes, which would give 1024
    there: a call's matmuls run one after another, the dense
    projections are past their ridge at 240 rows whatever the chunk,
    and the expert matrices stay read-bound until each sees 240.
  - *the bytes a call reads beside those weights that its rows do not
    turn into work*, as a share of theirs. ``models/latent_moe.py``
    holds 8 of 256 experts beside attention, a dense layer and shared
    experts that every row meets: the latter hold most of the bytes
    (3.5 GB beside 3.0 and a gathered embedding) and set the row's
    work, and the held experts' 3.0 GB are read by every call for the
    thirty-second of the assignments that reach them, which no chunk
    under 7680 rows makes compute-bound. Their read is paid once like
    the others': 240 x (1 + 3.0 / 3.5) = 449 rows.
  - *the work a call does once whatever its rows*, in rows' worth of
    the counted weights' work (both run at the chip's peak, so the
    ratio is the same on every chip). A latent-attention chunk makes
    keys and values of every row it attends to (``latent_expand``, 168
    MFLOP a row over 5 layers) before a row of its own is scored: at
    the rows a lane of ``max_seq`` holds on average over a prompt, half
    of them, that is 394 rows' worth at 16 384: 449 + 394 = 843 ->
    1024 rows.
  It is a rule of thumb, not a knee that was measured, and on a v5e
  each family's answer read otherwise than its premise has it: a llama
  call's time grew nearly in line with its rows from 128 on (attention
  over the whole cache and the activations grow with them) and 512
  rows served every llama cell better than 256 (PERF.md section 6,
  PR 29); the routed cell completes 39 % more tokens a second at 2048
  rows than at 256 and as many at 1024 as at 2048, the compiler's
  grouped matmul being far under either roof at any of them (PR 47);
  the latent cell completes 18 % more at 1024 rows than at 256, 3 %
  more than at 512, and at 2048 fewer than at 256, and not because a
  row got cheaper: a chunk call's time is in line with its rows at
  every size (its matmuls run at half the peak with the weights' read
  under them, and the expansion rides inside the scores' fusions), so
  what a larger chunk buys is the decode calls that no longer stand
  one behind every chunk (PR 49). What bounds a chunk from above is
  how long the decode step behind it may wait (a token's gap at the
  99th percentile is a quarter longer at 2048 rows than at 256 in the
  routed cell, and three times as long at 1024 as at 256 in the latent
  one), which no cell judges yet and no rule here accounts for. Three
  chunk buckets (C/4, C/2, C) bound compilations. The head runs on the
  one row a chunk returns logits for.
  ``warm_up()`` runs every program once, at every read window;
  ``LLMServer`` calls it before it takes a request, a bare engine
  compiles on first use.
- The model is the configuration's: ``config.model_module`` names the
  family's module, written on ``models/decoder.py``. It gives
  ``init_params``, ``init_cache``, ``forward_with_cache`` and
  ``attn_rows_read``; ``read_counters`` where its programs count on the
  device; ``chunk_terms`` where a call pays otherwise than by every row
  meeting every weight; ``read_choices`` where the configuration's
  programs leave in the cache what a call chose (experts, selected rows:
  ``config.says_choices``), which the engine hands on under the same
  name (None elsewhere). Nothing below knows what a cache holds: it is a
  pytree the programs take and return. Beside the signature the engine
  and the programs share one thing, ``decoder.idle_position``: the
  length a lane that is nobody's is dispatched at. A family whose cache
  holds a state and not rows by position (``models/hybrid_ssm.py``)
  owes that lane and a new sequence what rows get for free: its mixer
  leaves the state of a lane at the idle position, and behind a padded
  chunk's last token, exactly as it was (a lane that is mid-prefill
  rides every decode call between its chunks), and a call whose
  ``start`` is 0 begins from a zero state whatever the slot held.
- KV cache is preallocated per shard (L, B, KVH, max_seq, hd) and
  UPDATED IN PLACE: both programs are jitted with the cache donated,
  the cached forward carries it through its layer scan and writes only
  the new rows (a prefill chunk straight into its slot), so a call
  moves no more of the cache than attention reads. The buffer passed
  in is gone once a call is dispatched: every caller rebinds
  ``shard.cache`` from the result, and ``abort_all()`` re-allocates the
  cache of a shard whose failed call took it along. Per-slot lengths
  mask attention (models/llama.py forward_with_cache).
- Attention READS ONLY THE ROWS A LIVE SEQUENCE CAN ATTEND TO. Each
  program is compiled for two read windows, the whole cache and its
  first half (1024 / 2048 rows of a 2048-row cache), and a call runs
  the least that holds its rows: a whole chunk's ``start + bucket``, a
  decode's longest live lane plus the row it writes (a bucket under the
  chunk reads every row: its call is the weights' read). The choice is
  made on the host from the numpy arguments every caller hands over
  anyway (``_prefill`` / ``_decode``), so a short conversation in a long
  cache does not pay for the cache's length. A window that holds every
  attendable row gives the full read's result (a masked row weighs 0
  exactly). ``attn_rows_read`` / ``attn_rows_full`` says how much of the
  cache's length the calls read.
- Sampling (greedy / temperature) is jitted with the decode step; a
  prompt's first token is drawn the same way by ``first_token``, a
  program of a few instructions over the logits its last chunk returned.
- The host runs ONE decode step behind the device. ``step()`` first
  dispatches and reads nothing: every shard's prefill chunk, then every
  shard's decode N+1 (two shards' in one call where two have lanes to
  decode), whose ``last_tokens`` is the device array that
  shard's decode N returned (``shard.tokens``; ``first_token`` writes a
  finished prompt's first token into its lane there, so the lane joins
  without the host having seen it) and whose ``lengths`` is the host's
  own count. Only then does it read decode N's tokens (dispatched by the
  call before), book them, read the first token of a prompt this call
  finished (ready when its chunk ends, ahead of the decodes just
  queued), and return those pairs. While the caller hands tokens on and
  admits, the device runs N+1. Endings by count (``max_tokens``, the
  cache's end) are known before dispatch: such a lane is not dispatched
  again. An ``eos_id`` ending is known at the read, when the lane has
  ridden one decode more: that token is never read, emitted or appended
  (``lanes_discarded``), and the cache row it wrote lies beyond the
  freed slot's length, where the slot's next prompt or decode writes
  before anything attends. The queue is one decode a shard deep, never
  deeper, and empty whenever ``num_active()`` is 0. A greedy request's
  tokens are exactly those of a loop that reads every call before the
  next; a sampled one's are drawn from the same keys in dispatch order
  (all chunks of a step before its decodes). A call over a pair of
  shards is ONE dispatch: one draw over all its ``2 x max_batch`` lanes
  (by the backend's bit generator seeded from the engine's key, which
  lowers in two instructions; the next key comes out of the same
  stream), where two calls would have split the key twice, so a
  sampled request's draws depend on whether its shard decoded alone;
  a greedy one's tokens are the per-shard loop's up to what a matmul
  over twice the rows rounds otherwise. Each shard's tokens and cache
  come back as their own arrays and are rebound (``shard.tokens``,
  ``shard.cache``, ``shard.unread``) as after a call of its own.
- Observation: every request carries seven monotonic stamps (its six
  phases: ingress and accept before the server's pending queue, then
  queue_wait, prefill_wait, prefill, decode), ``EngineStats``
  counts what the steps did, and each part of ``step()`` is a
  ``tracing.phase`` span (``ray_tpu.llm.*`` in a profiler trace, on the
  device's clock). ``llm.decode_sync`` is the wait for step N's tokens
  while N+1 runs, ``llm.first_token_sync`` the wait for a chunk dispatched
  in the same call; a ``step()`` lasts what the device needs for one
  round of programs, not one call's dispatch-to-read. ``decode_ahead`` /
  ``decode_calls`` is how often the device had its next decode queued;
  ``decode_shards`` / ``decode_calls`` how many shards a decode call
  advanced (1.0: never a pair; 2.0: always), and a pair's
  ``llm.decode_dispatch`` span says ``shards=2`` beside its ``rows``.
  Why a computed lane stood empty is counted where the lanes are:
  ``decode_lanes_prefilling`` (its request's prompt is not all in) and
  ``decode_lanes_free`` (no request could have used it) make up
  ``decode_lanes_total`` with ``decode_lanes_active``, and the span says
  ``prefilling=`` and ``free=`` for its call.
  The jitted programs carry a ``sample`` scope next to the model's own
  (``kv_write``, ``kv_slice``, ``attn_cached``, ...).
"""

from __future__ import annotations

import math
import threading
import time
import weakref
from collections import deque
from dataclasses import dataclass, field
from functools import partial, wraps
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ray_tpu.util.tracing import PhaseStats, phase, recording

# a request's phases, each the gap between two of its stamps: the two
# before the server's pending queue (the handle's route entry to the
# replica's entry, that to the queue), then the engine's four
REQUEST_PHASES = ("ingress", "accept",
                  "queue_wait", "prefill_wait", "prefill", "decode")


def derived_prefill_chunk(device_kind: str, bytes_per_param: float,
                          max_seq: int, row_share: float = 1.0,
                          read_beside: float = 0.0,
                          once_rows: float = 0.0) -> int:
    """The prefill chunk for weights of ``bytes_per_param`` on a chip of
    ``device_kind``: the power of two nearest the rows whose work takes
    as long as what a call pays once, fitted to ``max_seq``. The work is
    the matmuls over the weights that hold most of a call's bytes (2
    FLOPs a row a parameter), and what is paid once is their read, so
    the rows are the chip's ridge: 240 of bf16 on a v5e. A model's
    ``chunk_terms`` adds what its configuration says:

    - ``row_share``, the share of a call's rows that multiply such a
      weight: 1 where every row meets every weight (240 -> 256 rows),
      ``experts_per_token / n_experts`` for routed experts (8 of 64:
      240 x 8 = 1920 -> 2048);
    - ``read_beside``, the bytes a call reads beside those weights for
      rows too few to make them work, as a share of those weights'
      bytes: paid once as well (8 of 256 experts held, 3.0 GB beside
      3.5: 240 x 1.87 = 449);
    - ``once_rows``, the work a call does once whatever its rows, over
      the work of one row in the counted weights: so many rows' worth
      on any chip (a latent chunk's expansion of the rows it attends
      to: 394 at ``max_seq`` 16 384, so 843 -> 1024).

    Measured on a v5e only; the other kinds' sizes follow from the
    table alone."""
    from ray_tpu._private.accelerators.tpu import flops_per_hbm_byte

    ridge = flops_per_hbm_byte(device_kind) * bytes_per_param / 2
    rows = ridge / row_share * (1 + read_beside) + once_rows
    return _fit_chunk(2 ** round(math.log2(rows)), max_seq)


def _fit_chunk(chunk: int, max_seq: int) -> int:
    """chunk must divide max_seq: chunk starts are then always aligned
    and a padded chunk bucket can never run past the cache end
    (dynamic_update_slice would CLAMP the start backward and overwrite
    earlier valid rows)."""
    chunk = min(chunk, max_seq)
    while max_seq % chunk:
        chunk //= 2
    return chunk


@dataclass
class GenRequest:
    request_id: str
    prompt_ids: List[int]
    max_tokens: int = 64
    temperature: float = 0.0
    eos_id: Optional[int] = None
    adapter_id: str = ""  # LoRA adapter ("" = base model)
    # filled during generation
    slot: int = -1
    shard: int = -1
    prefill_pos: int = 0  # prompt tokens already written to cache
    generated: List[int] = field(default_factory=list)
    done: bool = False
    # time.monotonic() stamps, 0.0 until reached: the handle's
    # ``_route`` entered (in this process's clock), the replica's method
    # entered, put on the server's pending queue, given a slot, first
    # chunk dispatched, first token read by the host, finished. A
    # request nobody routed (a bare engine, a server called directly)
    # has both of the first two at ``submitted``
    routed: float = 0.0
    received: float = 0.0
    submitted: float = 0.0
    admitted: float = 0.0
    prefill_started: float = 0.0
    first_token: float = 0.0
    finished: float = 0.0
    prefill_chunks: int = 0
    # written by the request's own thread in ``LLMServer.generate_stream``
    # alone: when it had the first token in hand, and the seconds and
    # count of its tokens' waits between the batching loop's read of
    # their step and that thread's ``q.get`` returning; of those seconds,
    # the ones a token lay in the queue before the thread came back to
    # ask for it (``backlog_s``: the rest is the thread's wake); and the
    # seconds and count of its ``yield``s, from handing a token to the
    # runtime to being resumed for the next
    first_yielded: float = 0.0
    handoff_s: float = 0.0
    handoff_n: int = 0
    backlog_s: float = 0.0
    yield_s: float = 0.0
    yield_n: int = 0

    def phases(self) -> Dict[str, float]:
        """Seconds in ingress (route entry to replica entry), accept
        (the replica's own work before the pending queue), queue_wait
        (no slot yet), prefill_wait (a slot, behind the other prompts of
        its shard), prefill and decode; they sum to finished - routed."""
        stamps = (self.routed, self.received, self.submitted, self.admitted,
                  self.prefill_started, self.first_token, self.finished)
        return {name: b - a for name, a, b in
                zip(REQUEST_PHASES, stamps, stamps[1:])}


@dataclass
class _Shard:
    """One (B, max_seq) KV cache block plus its slot bookkeeping.
    ``lengths`` counts a slot's cache rows as the host has dispatched
    them, which is one decode ahead of what it has read."""

    cache: Any
    lengths: np.ndarray
    free_slots: List[int]
    index: int = 0
    # a request stays active until its last token has been read
    active: Dict[int, GenRequest] = field(default_factory=dict)
    prefilling: "deque[GenRequest]" = field(default_factory=deque)
    # every lane's last token, on the device: what the newest decode
    # returned, a finished prompt's first token written into its lane
    tokens: Any = None
    # the decode dispatched and not read yet: its tokens and the lanes
    # (slot, request) it advanced
    unread: Optional[Tuple[Any, List[Tuple[int, GenRequest]]]] = None
    # a finished prompt's first token, not read yet: (request, scalar)
    first: Optional[Tuple[GenRequest, Any]] = None
    # the device counters of this shard's cache when last read
    counted: Dict[str, int] = field(default_factory=dict)


class EngineStats:
    """What the engine has done since it was built. Written under the
    engine's lock; every number only ever rises, so a reader takes two
    snapshots and subtracts. ``phases`` holds the seconds and counts of
    the parts of ``step()``; ``requests`` the last few thousand finished
    requests as (submitted stamp, queue_wait, prefill_wait, prefill,
    decode seconds, prefill chunks, ingress, accept seconds): the two
    phases before ``submitted`` stand last, so a reader by index of the
    first six reads what it read."""

    COUNTERS = (
        "steps", "tokens_emitted",
        "prefill_chunks",
        "prefill_tokens", "prefill_rows",  # real tokens; the buckets' rows
        # cache rows a sequence the prefill and decode calls read for
        # attention (their read windows), and max_seq a call; counted
        # where the window is chosen (``_prefill`` / ``_decode``)
        "attn_rows_read", "attn_rows_full",
        "decode_calls", "decode_lanes_active", "decode_lanes_total",
        # why a lane a decode call computed stood empty, counted with the
        # two above so that active + prefilling + free = total on every
        # call: the slots of requests still in ``shard.prefilling``
        # (admitted, the prompt not all in: the lane waits for the
        # engine's chunks), and the rest, which no request could have
        # used at that call (slots nobody holds: nothing was there to
        # admit; and, one call a request, the slot of a request whose
        # last token is dispatched and not read yet)
        "decode_lanes_prefilling", "decode_lanes_free",
        # shards the decode calls advanced: one a call, two where a call
        # ran over a pair, so over ``decode_calls`` how often it did
        "decode_shards",
        # decode calls dispatched while the shard's last one was unread;
        # lane-steps computed for a request that had already ended
        "decode_ahead", "lanes_discarded",
        "shards_grown", "requests_finished", "requests_refused",
        # a routed family's expert layers, summed over layers and calls:
        # rows that are somebody's tokens (no padding of a chunk, no idle
        # lane) times experts per token; experts with such a row or
        # more; experts held. Accumulated on the device, in the cache the
        # programs carry, and read when a snapshot is taken
        # (``device_counters``); 0 for a family that has none
        "moe_assignments", "moe_experts_touched", "moe_expert_slots",
    )

    def __init__(self, ring: int = 4096):
        for name in self.COUNTERS:
            setattr(self, name, 0)
        self.phases = PhaseStats()
        self.requests: "deque[tuple]" = deque(maxlen=ring)
        # set by the engine: () -> {counter: total so far} of what its
        # programs count on the device; a snapshot waits for them,
        # ``step()`` never does
        self.device_counters = None

    def snapshot(self) -> Dict[str, Any]:
        counted = {}
        if self.device_counters is not None:
            counted = self.device_counters()
            for name, total in counted.items():
                setattr(self, name, total)
        # a model's further counters (models/latent_moe.py: the rows its
        # two attention forms attend to) stand under the names it gives
        out: Dict[str, Any] = {n: getattr(self, n)
                               for n in (*self.COUNTERS, *counted)}
        out["phases"] = self.phases.snapshot()
        out["requests"] = list(self.requests)
        return out


class LlamaEngine:
    def __init__(
        self,
        config,
        params,
        *,
        max_batch: int = 8,
        max_seq: int = 512,
        seed: int = 0,
        prefill_chunk: Optional[int] = None,
        max_slots: Optional[int] = None,
    ):
        import importlib

        import jax
        import jax.numpy as jnp

        from ray_tpu.models.decoder import idle_position

        model = importlib.import_module(config.model_module)

        self.config = config
        self.params = params
        self.max_batch = max_batch
        self.max_seq = max_seq
        self._idle = idle_position(max_seq)     # an idle lane's length
        if prefill_chunk is None:
            leaves = jax.tree_util.tree_leaves(params)
            terms = (model.chunk_terms(config, max_seq)
                     if hasattr(model, "chunk_terms") else {})
            self.prefill_chunk = derived_prefill_chunk(
                jax.devices()[0].device_kind,
                sum(a.nbytes for a in leaves) / sum(a.size for a in leaves),
                max_seq, **terms)
        else:
            self.prefill_chunk = _fit_chunk(prefill_chunk, max_seq)
        # growth is whole-shard; round the cap to shard granularity so
        # the KV-memory bound it expresses actually holds
        want_slots = max_slots or 4 * max_batch
        self.max_slots = max(max_batch, (want_slots // max_batch) * max_batch)
        self._rng = jax.random.PRNGKey(seed)
        self._jax = jax
        self._model = model
        self.stats = EngineStats()
        self.shards: List[_Shard] = []
        # most slots one decode call has advanced so far: whether
        # requests were ever batched, not merely queued
        self.peak_active = 0

        # prefill-chunk buckets: a call reads all the weights whatever
        # its rows, so a bucket far under the chunk saves little
        c = self.prefill_chunk
        self.buckets = [b for b in (c // 4, c // 2) if b >= 16] + [c]
        # read windows: attention reads the first `rows` rows of a
        # sequence's cache, the least of these that holds every row a
        # call can attend to: the whole cache and, where a chunk fits
        # it, its first half. No finer: every variant of a program is
        # 0.2 to 0.7 s of a replica's start (traced, lowered, loaded),
        # and the first halving is most of what there is to gain
        # (PERF.md section 6, PR 35)
        self.windows = [max_seq]
        if max_seq % 2 == 0 and max_seq // 2 >= c:
            self.windows.insert(0, max_seq // 2)
        self.shards.append(self._new_shard())
        self._rows_read = {
            w: model.attn_rows_read(config, self.shards[0].cache, w)
            for w in self.windows}
        # what caches that a failed call took along had counted when
        # last read (``abort_all``): the totals only ever rise
        self._counted_before: Dict[str, int] = {}
        self.read_choices = (model.read_choices if getattr(
            config, "says_choices", False) else None)
        if hasattr(model, "read_counters"):
            # through a weak reference: the engine, its weights and its
            # caches go when their last holder lets go, with no cycle
            # for the collector to find first
            me = weakref.ref(self)
            self.stats.device_counters = lambda: (
                me()._device_counters() if me() is not None else {})

        def prefill(params, cache, tokens, slot_onehot, start, length,
                    bucket, rows):
            # tokens (1, bucket) padded; writes into the slot's rows at
            # offset `start` and returns logits at the chunk's last real
            # token (used only when the chunk completes the prompt);
            # attends to the slot's first `rows` rows
            del bucket
            logits, new_cache = model.forward_with_cache(
                params, tokens, cache, start, config,
                slot=jnp.argmax(slot_onehot),
                logits_at=jnp.reshape(length - 1, (1,)), rows=rows,
            )
            return logits[0, 0], new_cache

        def decode(params, cache, last_tokens, lengths, temps, rng, rows):
            # one token for every slot: tokens (B,), lengths (B,) = count
            # already in cache; inactive slots just waste a lane. Or two
            # shards at once: ``cache`` and ``last_tokens`` a pair, lengths
            # and temps of both shards' lanes, shard after shard
            pair = isinstance(cache, tuple)
            if pair:
                last_tokens = jnp.concatenate(last_tokens)
            logits, new_cache = model.forward_with_cache(
                params, last_tokens[:, None], cache, lengths, config,
                rows=rows,
            )
            with jax.named_scope("sample"):
                logits = logits[:, 0]  # (B, V)
                greedy = jnp.argmax(logits, axis=-1)
                if pair:
                    sampled, key = draw_block(rng, logits, temps)
                else:
                    keys = jax.random.split(rng, logits.shape[0] + 1)
                    sampled = jax.vmap(
                        lambda k, lg, t: jax.random.categorical(
                            k, lg / jnp.maximum(t, 1e-4))
                    )(keys[1:], logits, temps)
                    key = keys[0]
                toks = jnp.where(temps > 0, sampled, greedy).astype(jnp.int32)
                if pair:
                    toks = tuple(jnp.split(toks, len(cache)))
                return toks, new_cache, key

        def draw_block(rng, logits, temps):
            # one draw over all the rows by the backend's bit generator,
            # seeded from the engine's key, and the next key out of the
            # same stream: two instructions to lower where the threefry
            # of ``jax.random`` unrolls into hundreds (0.65 s of a decode
            # variant's lowering at every start: PERF.md section 6, PR 54)
            state = jnp.concatenate([rng, rng ^ jnp.uint32(0x9E3779B9)])
            state, bits = jax.lax.rng_bit_generator(state, logits.shape)
            _, key = jax.lax.rng_bit_generator(state, rng.shape)
            uniform = (bits >> 9).astype(jnp.float32) * 2.0 ** -23  # [0, 1)
            gumbel = -jnp.log(-jnp.log(jnp.maximum(uniform, 1e-20)))
            scaled = logits / jnp.maximum(temps, 1e-4)[:, None]
            return jnp.argmax(scaled + gumbel, axis=-1), key

        def first_token(tokens, logits, slot, temp, rng):
            # a finished prompt's first token, drawn as decode draws and
            # written into its lane of the shard's token vector; the key
            # moves only for a sampled request
            with jax.named_scope("sample"):
                keys = jax.random.split(rng)
                sampled = jax.random.categorical(
                    keys[1], logits / jnp.maximum(temp, 1e-4))
                tok = jnp.where(temp > 0, sampled, jnp.argmax(logits))
                tok = tok.astype(jnp.int32)
                return (tokens.at[slot].set(tok), tok,
                        jnp.where(temp > 0, keys[0], rng))

        self._program_fns = (prefill, decode, first_token)
        self._jit_prefill, self._jit_decode, self._first_token = (
            self._jit_programs(*self._program_fns))
        # the variants run so far: (bucket, rows) of prefill, rows of decode
        self._prefills_run: set = set()
        self._decodes_run: set = set()
        self._pair_run = False                  # decode over a pair
        self._lock = threading.Lock()

    def _jit_programs(self, prefill, decode, first_token):
        """The cache (argument 1) is donated: with the model's carried
        scan the programs update it in place, and the buffer a caller
        passed in is gone once the call is dispatched."""
        jit = self._jax.jit
        return (jit(prefill, static_argnames=("bucket", "rows"),
                    donate_argnums=(1,)),
                jit(decode, static_argnames=("rows",), donate_argnums=(1,)),
                jit(first_token))

    def _prefill_variants(self) -> List[Tuple[int, int]]:
        """Every (bucket, rows) ``prefill_window`` can choose."""
        return [(b, w) for b in self.buckets
                for w in (self.windows if b == self.prefill_chunk
                          else [self.max_seq])]

    def _window(self, need: int) -> int:
        """The least read window of ``need`` rows or more."""
        return next(w for w in self.windows if w >= need)

    def prefill_window(self, start, bucket: int) -> int:
        """Rows a prefill call reads: a whole chunk ends at ``start`` +
        ``bucket`` and attends to nothing behind. A bucket under the
        chunk (a prompt's last rows) reads every row: such a call is the
        weights' read, its attention a few per cent whatever the rows,
        and a window's variant of it would not pay for its place in a
        replica's start. ``start`` is the (1,) numpy array the call is
        handed."""
        if bucket < self.prefill_chunk:
            return self.max_seq
        return self._window(int(start[0]) + bucket)

    def decode_window(self, lengths) -> int:
        """Rows a decode call reads: a lane of ``lengths[b]`` rows
        writes one more and attends to all of them. Idle lanes carry the
        idle position, the scratch row, and what they compute is
        dropped; a live lane is dispatched two rows short of it at most
        (``_last_by_count``). ``lengths`` is the numpy array the call is
        handed."""
        live = lengths[lengths != self._idle]
        return self._window(int(live.max()) + 1) if live.size else self.max_seq

    # The two programs as every caller knows them (``step()``, the
    # benchmark's probe): the read window is chosen here, on the host,
    # from the numpy arguments they pass anyway, and counted here, so
    # that ``attn_rows_read`` is what was dispatched whoever called.
    def _count_rows(self, rows: int) -> None:
        # the layers' mean: a layer that keeps a window's rows in a ring
        # reads the ring whatever the window (models/window_moe.py)
        self.stats.attn_rows_read += self._rows_read[rows]
        self.stats.attn_rows_full += self.max_seq

    def _device_counters(self) -> Dict[str, int]:
        """What the programs have counted in every shard's cache, with
        what dropped caches had; under the lock, so that no call takes
        a cache away between finding it and reading it."""
        with self._lock:
            total = dict(self._counted_before)
            for shard in self.shards:
                if not self._cache_gone(shard):
                    shard.counted = self._model.read_counters(shard.cache)
                for name, n in shard.counted.items():
                    total[name] = total.get(name, 0) + n
            return total

    def _prefill(self, params, cache, tokens, slot_onehot, start, length, *,
                 bucket):
        """-> (logits (V,) of the chunk's last real token, cache)."""
        rows = self.prefill_window(start, bucket)
        self._prefills_run.add((bucket, rows))
        self._count_rows(rows)
        return self._jit_prefill(params, cache, tokens, slot_onehot, start,
                                 length, bucket=bucket, rows=rows)

    def _decode(self, params, cache, last_tokens, lengths, temps, rng):
        """-> (tokens (B,), cache, the next sampling key)."""
        rows = self.decode_window(lengths)
        self._decodes_run.add(rows)
        self._count_rows(rows)
        return self._jit_decode(params, cache, last_tokens, lengths, temps,
                                rng, rows=rows)

    def _decode_pair(self, params, caches, last_tokens, lengths, temps, rng):
        """One decode over two shards: ``caches`` and ``last_tokens``
        pairs, ``lengths`` and ``temps`` (2 x max_batch,) of the first
        shard's lanes and then the second's -> (the two token vectors,
        the two caches, the next sampling key). At the top read window
        whatever the lanes hold: a variant a window is 1.3 to 2.5 s of a
        replica's start where a call's rows are 16 (PERF.md section 6,
        PR 54), and the rows a lower window would spare are a few per
        cent of the weights the pair reads once."""
        self._pair_run = True
        for _ in caches:
            self._count_rows(self.max_seq)
        return self._jit_decode(params, caches, last_tokens, lengths, temps,
                                rng, rows=self.max_seq)

    def _cache_gone(self, shard: _Shard) -> bool:
        """Whether a call that failed after it was dispatched took the
        shard's donated cache with it."""
        return any(leaf.is_deleted() for leaf in
                   self._jax.tree_util.tree_leaves(shard.cache))

    def _new_cache(self):
        return self._model.init_cache(
            self.config, self.max_batch, self.max_seq, self.prefill_chunk)

    def _new_tokens(self):
        return self._jax.device_put(np.zeros(self.max_batch, np.int32))

    def _new_shard(self) -> _Shard:
        return _Shard(
            cache=self._new_cache(),
            lengths=np.zeros(self.max_batch, dtype=np.int32),
            free_slots=list(range(self.max_batch)),
            index=len(self.shards),
            tokens=self._new_tokens(),
        )

    def warm_up(self) -> None:
        """Run every program once, in every variant ``step()`` can ask
        for (the whole chunk at each read window and the smaller buckets
        at the top one, into slot 0 of the first shard; a first token off
        the last one's logits; then decode at each window on the scratch
        row, its tokens on the device as ``step()`` passes them; where
        ``max_slots`` allows a second shard, the decode over a pair as
        well, the pair's other cache made here and dropped) and wait for
        them, so that no request pays a compile.
        Each variant is asked for by name, not through the host's choice.
        Counts nothing in ``stats`` and leaves the sampling key as it
        was; the rows it writes are overwritten by the slot's next prompt
        before anything attends to them. The engine must be idle."""
        with self._lock:
            if self.num_active():
                raise RuntimeError("warm_up needs an idle engine")
            shard = self.shards[0]
            onehot = np.zeros(self.max_batch, np.float32)
            onehot[0] = 1.0
            for bucket, rows in self._prefill_variants():
                logits, shard.cache = self._jit_prefill(
                    self.params, shard.cache, np.zeros((1, bucket), np.int32),
                    onehot, np.zeros(1, np.int32), 1, bucket=bucket, rows=rows)
            tokens, _, _ = self._first_token(
                shard.tokens, logits, np.int32(0), np.float32(0), self._rng)
            idle = np.full(self.max_batch, self._idle, np.int32)
            temps = np.zeros(self.max_batch, np.float32)
            for rows in self.windows:
                toks, shard.cache, _ = self._jit_decode(
                    self.params, shard.cache, tokens, idle, temps, self._rng,
                    rows=rows)
            pair = self.max_slots >= 2 * self.max_batch    # a second shard
            if pair:
                _, (shard.cache, _), _ = self._jit_decode(
                    self.params, (shard.cache, self._new_cache()),
                    (toks, tokens), np.tile(idle, 2), np.tile(temps, 2),
                    self._rng, rows=self.max_seq)
            self._jax.block_until_ready((toks, shard.cache))
        self._prefills_run.update(self._prefill_variants())
        self._decodes_run.update(self.windows)
        self._pair_run = self._pair_run or pair

    # ------------------------------------------------------------------
    def has_capacity(self) -> bool:
        if any(s.free_slots for s in self.shards):
            return True
        return len(self.shards) * self.max_batch < self.max_slots

    def num_active(self) -> int:
        """Requests with a slot. At 0 nothing is in flight either: a
        request is active until its last token has been read, and a
        result that no live request waits for is dropped in ``step()``."""
        return sum(
            len(s.active) + len(s.prefilling) for s in self.shards
        )

    def in_flight_requests(self) -> List[GenRequest]:
        out: List[GenRequest] = []
        for s in self.shards:
            out.extend(s.active.values())
            out.extend(s.prefilling)
        return out

    def abort_all(self) -> List[GenRequest]:
        """Drop every in-flight request (engine fault path) and every
        result not read yet; returns the requests so the caller can fail
        their waiters. A call that failed after it was dispatched has
        taken the shard's donated cache with it: such a shard gets a new
        one, so the engine can go on."""
        with self._lock:
            dropped = self.in_flight_requests()
            for s in self.shards:
                for slot in list(s.active):
                    self._release(s, s.active.pop(slot))
                while s.prefilling:
                    self._release(s, s.prefilling.popleft())
                # a failed call's tokens would fail the next one too
                s.unread = s.first = None
                s.tokens = self._new_tokens()
                if self._cache_gone(s):
                    s.cache = self._new_cache()
                    for name, n in s.counted.items():
                        self._counted_before[name] = (
                            self._counted_before.get(name, 0) + n)
                    s.counted = {}
            return dropped

    def add_request(self, req: GenRequest) -> bool:
        """Admit into a free slot. No model compute happens here — the
        prompt prefills chunk-by-chunk inside step(), interleaved with
        decode, so admission never stalls running sequences."""
        with self._lock:
            if len(req.prompt_ids) >= self.max_seq:
                raise ValueError(
                    f"prompt length {len(req.prompt_ids)} >= max_seq {self.max_seq}"
                )
            si = next(
                (i for i, s in enumerate(self.shards) if s.free_slots), None
            )
            if si is None:
                if len(self.shards) * self.max_batch >= self.max_slots:
                    self.stats.requests_refused += 1
                    return False
                self.shards.append(self._new_shard())  # slot growth
                self.stats.shards_grown += 1
                si = len(self.shards) - 1
            shard = self.shards[si]
            req.slot = shard.free_slots.pop()
            req.shard = si
            req.prefill_pos = 0
            req.admitted = time.monotonic()
            # handed to the engine directly, or by a caller no handle
            # routed: the stamps it lacks are the next one's
            req.submitted = req.submitted or req.admitted
            req.received = req.received or req.submitted
            req.routed = req.routed or req.received
            shard.prefilling.append(req)
            return True

    def _release(self, shard: _Shard, req: GenRequest):
        req.done = True
        shard.lengths[req.slot] = 0
        shard.free_slots.append(req.slot)

    def _finish(self, shard: _Shard, slot: int):
        req = shard.active.pop(slot)
        self._release(shard, req)
        req.finished = time.monotonic()
        self.stats.requests_finished += 1
        ingress, accept, *engine_phases = req.phases().values()
        self.stats.requests.append(
            (req.submitted, *engine_phases, req.prefill_chunks,
             ingress, accept))

    def _pump_prefill(self, shard: _Shard, out: List[Tuple[GenRequest, int]]):
        """Write ONE chunk of the oldest pending prompt into the cache;
        on prompt completion its first token is drawn on the device and
        written into its lane, which joins the next decode without the
        host having seen it. ``step()`` reads it (``shard.first``) and
        puts it in ``out``."""
        if not shard.prefilling:
            return
        req = shard.prefilling[0]
        stats = self.stats
        n = len(req.prompt_ids)
        pos = req.prefill_pos
        chunk = min(self.prefill_chunk, n - pos)
        # ``rows``: the tokens this call carries, as a decode's span says
        # its live lanes: what a trace of a few steps was asked for.
        # ``start``: the row of the sequence they begin at, so that a
        # trace also says how many rows the call's attention had to see
        with phase("llm.prefill_dispatch", stats.phases,
                   request_id=req.request_id, shard=shard.index, rows=chunk,
                   start=pos):
            bucket = next(b for b in self.buckets if b >= chunk)
            tokens = np.zeros((1, bucket), np.int32)
            tokens[0, :chunk] = req.prompt_ids[pos:pos + chunk]
            onehot = np.zeros(self.max_batch, np.float32)
            onehot[req.slot] = 1.0
            if not req.prefill_started:
                req.prefill_started = time.monotonic()
            last_logits, shard.cache = self._prefill(
                self.params, shard.cache, tokens, onehot,
                np.asarray([pos], np.int32), chunk, bucket=bucket,
            )
            if pos + chunk == n:  # prompt complete: the lane goes live
                shard.tokens, tok, self._rng = self._first_token(
                    shard.tokens, last_logits, np.int32(req.slot),
                    np.float32(req.temperature), self._rng)
                shard.prefilling.popleft()
                shard.lengths[req.slot] = n
                shard.active[req.slot] = req
                shard.first = (req, tok)
        req.prefill_pos = pos + chunk
        req.prefill_chunks += 1
        stats.prefill_chunks += 1
        stats.prefill_tokens += chunk
        stats.prefill_rows += bucket

    def _last_by_count(self, req: GenRequest, count: int) -> bool:
        """Whether a request's ``count``-th token is its last whatever
        it is: ``max_tokens`` reached, or the cache row before the
        scratch row (a prompt that ends there or on it gets its first
        token and no decode, so no live lane is ever dispatched with the
        scratch row's number for a length, which is how
        ``decode_window`` tells the idle lanes). Known before the token
        is."""
        return (count >= req.max_tokens
                or len(req.prompt_ids) + count >= self._idle)

    def _lanes_to_decode(self, shard: _Shard) -> List[Tuple[int, GenRequest]]:
        """The shard's lanes (slot, request) that have a token to come:
        their first, and a decode for every row written since."""
        return [(slot, req) for slot, req in shard.active.items()
                if not self._last_by_count(
                    req, shard.lengths[slot] - len(req.prompt_ids) + 1)]

    def _prepare_decode(self, shard: _Shard, lanes):
        """-> (lengths, temps) of the shard's lanes for a decode of
        ``lanes``, each of which is accounted the row it will write."""
        with phase("llm.decode_prepare", self.stats.phases, shard=shard.index):
            temps = np.zeros(self.max_batch, np.float32)
            # inactive lanes (free, mid-prefill or at their last token)
            # still ride the batched decode; point their cache write at
            # the scratch row (the idle position, provably never
            # attended: sequences finish before reaching it) so they
            # cannot corrupt a half-prefilled prompt's rows
            lens = np.full(self.max_batch, self._idle, np.int32)
            for slot, req in lanes:
                temps[slot] = req.temperature
                lens[slot] = shard.lengths[slot]
                # the decode consumes the lane's last token: account it
                shard.lengths[slot] += 1
        return lens, temps

    def _dispatch_decode(self, group: List[Tuple[_Shard, list]]):
        """Dispatch ONE decode for the lanes to decode of one shard or
        of two (``group``: (shard, lanes) of each), fed the device's own
        last tokens, and rebind each shard's tokens and cache from what
        it returns. One shard goes through ``_decode``, two through
        ``_decode_pair``: the same program over twice the lanes, every
        weight read once."""
        stats = self.stats
        shards = [shard for shard, _ in group]
        live = sum(len(lanes) for _, lanes in group)
        total = len(group) * self.max_batch
        prefilling = sum(len(shard.prefilling) for shard in shards)
        free = total - live - prefilling
        self.peak_active = max(self.peak_active, live)
        lens, temps = zip(*(self._prepare_decode(*each) for each in group))
        # ``attended``, where a trace records the span: the rows its live
        # lanes attend to, all together (each its own length and the row
        # it writes)
        seen = {"attended": live + int(sum(
            of_shard[slot] for of_shard, (_, lanes) in zip(lens, group)
            for slot, _ in lanes))} if recording() else {}
        with phase("llm.decode_dispatch", stats.phases, shard=shards[0].index,
                   shards=len(group), rows=live, prefilling=prefilling,
                   free=free, **seen):
            if len(group) == 1:
                shard, = shards
                shard.tokens, shard.cache, self._rng = self._decode(
                    self.params, shard.cache, shard.tokens,
                    lens[0], temps[0], self._rng,
                )
            else:
                tokens, caches, self._rng = self._decode_pair(
                    self.params, tuple(s.cache for s in shards),
                    tuple(s.tokens for s in shards),
                    np.concatenate(lens), np.concatenate(temps), self._rng)
                for shard, toks, cache in zip(shards, tokens, caches):
                    shard.tokens, shard.cache = toks, cache
        stats.decode_calls += 1
        stats.decode_shards += len(group)
        stats.decode_ahead += any(s.unread is not None for s in shards)
        stats.decode_lanes_active += live
        stats.decode_lanes_prefilling += prefilling
        stats.decode_lanes_free += free
        stats.decode_lanes_total += total

    def _dispatch_decodes(self) -> List[Optional[tuple]]:
        """Every shard's decode, the shards with lanes to decode two to
        a call in their order (a third rides with a fourth or alone: a
        call over every live shard would want a program a count) ->
        what each shard's ``unread`` will hold, None where no lane
        wanted a decode."""
        ready = [(shard, lanes) for shard in self.shards
                 if (lanes := self._lanes_to_decode(shard))]
        for i in range(0, len(ready), 2):
            self._dispatch_decode(ready[i:i + 2])
        ahead: List[Optional[tuple]] = [None] * len(self.shards)
        for shard, lanes in ready:
            ahead[shard.index] = (shard.tokens, lanes)
        return ahead

    def _take(self, shard: _Shard, req: GenRequest, tok: int,
              out: List[Tuple[GenRequest, int]]):
        req.generated.append(tok)
        out.append((req, tok))
        if (req.eos_id is not None and tok == req.eos_id
                or self._last_by_count(req, len(req.generated))):
            self._finish(shard, req.slot)

    def step(self) -> List[Tuple[GenRequest, int]]:
        """One engine step, the host one decode behind the device (the
        module docstring has the order and why). Dispatches every shard's
        prefill chunk and decode, then returns the (request, token) pairs
        of the decodes the call BEFORE this one dispatched and the first
        token of a prompt this call finished. A call that finds nothing
        unread (the first after an idle engine) returns no decode token;
        while the caller hands the pairs on and admits, the device runs
        the decodes of this call."""
        stats = self.stats
        with self._lock, phase("llm.step", stats.phases):
            out: List[Tuple[GenRequest, int]] = []
            for shard in self.shards:
                self._pump_prefill(shard, out)
            ahead = self._dispatch_decodes()
            for shard in self.shards:
                if shard.unread is None:
                    continue
                toks, lanes = shard.unread
                with phase("llm.decode_sync", stats.phases,
                           shard=shard.index):
                    toks = np.asarray(toks)
                with phase("llm.decode_bookkeep", stats.phases,
                           shard=shard.index):
                    for slot, req in lanes:
                        self._take(shard, req, int(toks[slot]), out)
            for shard in self.shards:
                if shard.first is None:
                    continue
                req, tok = shard.first
                shard.first = None
                with phase("llm.first_token_sync", stats.phases,
                           request_id=req.request_id, shard=shard.index):
                    tok = int(tok)
                req.first_token = time.monotonic()
                self._take(shard, req, tok, out)
            for shard, unread in zip(self.shards, ahead):
                # a request an eos_id ended at this read has ridden the
                # decode just dispatched: that token is never read (the
                # cache row it wrote lies beyond anything attended to)
                if unread is not None:
                    toks, lanes = unread
                    live = [(slot, req) for slot, req in lanes if not req.done]
                    stats.lanes_discarded += len(lanes) - len(live)
                    unread = (toks, live) if live else None
                shard.unread = unread
            stats.steps += 1
            stats.tokens_emitted += len(out)
            return out

    def compiled_programs(self) -> Dict[str, Any]:
        """The engine's programs as compiled executables, one for each
        variant run so far: ``first_token``, ``decode_<rows>`` for each
        read window (``decode_<max_seq>_x2``: over a pair of shards) and
        ``prefill_<bucket>_<rows>`` for each chunk bucket at each. For
        reading their text (``jax_utils.scope_map``) next to a device
        trace, where every variant of a program runs under the one name
        it was jitted under. Each is jitted afresh and
        compiled again (or loaded from the persistent cache;
        ``compile_with_scopes`` says why), so call this outside anything
        timed."""
        from ray_tpu._private.jax_utils import compile_with_scopes

        # new function objects under the old names: new traces, new modules
        prefill, decode, first_token = self._jit_programs(
            *(wraps(fn)(partial(fn)) for fn in self._program_fns))
        cache = self.shards[0].cache
        i32, f32 = np.int32, np.float32
        tokens = np.zeros(self.max_batch, i32)
        out = {"first_token": compile_with_scopes(first_token.lower(
            tokens, np.zeros(self.config.vocab_size, f32), i32(0), f32(0),
            self._rng))}
        for rows in sorted(self._decodes_run):
            out[f"decode_{rows}"] = compile_with_scopes(decode.lower(
                self.params, cache, tokens, np.zeros(self.max_batch, i32),
                np.zeros(self.max_batch, f32), self._rng, rows=rows))
        if self._pair_run:
            out[f"decode_{self.max_seq}_x2"] = compile_with_scopes(
                decode.lower(
                    self.params, (cache, cache), (tokens, tokens),
                    np.zeros(2 * self.max_batch, i32),
                    np.zeros(2 * self.max_batch, f32), self._rng,
                    rows=self.max_seq))
        for bucket, rows in sorted(self._prefills_run):
            out[f"prefill_{bucket}_{rows}"] = compile_with_scopes(
                prefill.lower(
                    self.params, cache, np.zeros((1, bucket), i32),
                    np.zeros(self.max_batch, f32), np.zeros(1, i32), 1,
                    bucket=bucket, rows=rows))
        return out

    # ------------------------------------------------------------------
    def generate(self, prompt_ids: List[int], *, max_tokens: int = 64,
                 temperature: float = 0.0, eos_id: Optional[int] = None
                 ) -> List[int]:
        """Synchronous single-prompt convenience (batch path: step())."""
        req = GenRequest(
            request_id="sync", prompt_ids=list(prompt_ids),
            max_tokens=max_tokens, temperature=temperature, eos_id=eos_id,
        )
        ok = self.add_request(req)
        assert ok, "engine full"
        while not req.done:
            self.step()
        return req.generated
