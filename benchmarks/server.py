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

from . import holder, serve_load, spec, traffic


@dataclasses.dataclass
class SeededLLMConfig(LLMConfig):
    """Weights from ``--seed`` in one jitted program, in the type they
    are served in (``LLMConfig.load_params`` fixes PRNGKey(0))."""

    seed: int = 0
    rehearse: bool = False
    hp: dict = dataclasses.field(default_factory=dict)  # the configuration

    def load_params(self):
        from functools import partial

        import jax

        family = spec.family_of(self.hp)
        init = jax.jit(partial(family.init_params, cfg=self.model_config))
        return init(spec.prng_key(self.seed))


def _new_counters() -> dict:
    return {"engine_steps": 0, "engine_tokens": 0, "engine_step_s": 0.0,
            "prefill_s": 0.0, "engine_step_ms": [], "prefill_chunks": 0}


# how many positions a serving cell's comparison reads where its
# ``reference_check`` block does not say
POSITIONS, DECODE_STEPS = 32, 16


def room_for_a_copy(cache) -> None:
    """``probe_rows`` keeps one copy of the cache alive beside the real
    one, so on every chip it wants as many bytes free as the cache holds
    there, and a program's temporaries over that, as any call does.
    Where a chip says how much it has (``memory_stats``; the CPU of a
    rehearsal says nothing) and that is less, this raises and says so,
    before the first call: an allocation that failed inside a program
    would read as a run that failed, not as a cell whose comparison has
    no room. README.md, "A served family", has the sum a cell's author
    makes: weights + 2 x cache + temporaries, and then the reference's
    float32 pass beside the weights and one cache."""
    import jax

    held = {}
    for leaf in jax.tree_util.tree_leaves(cache):
        for part in leaf.addressable_shards:
            held[part.device] = held.get(part.device, 0) + part.data.nbytes
    for device, need in held.items():
        stats = device.memory_stats() or {}
        if "bytes_limit" not in stats:
            continue
        free = stats["bytes_limit"] - stats.get("bytes_in_use", 0)
        if free < need:
            raise RuntimeError(
                f"reference_check: the probe reads its rows on a copy of the "
                f"cache, {need} bytes on {device}, and {free} of "
                f"{stats['bytes_limit']} are free there: the cell's weights "
                "and two caches have to fit the chip (benchmarks/README.md, "
                "'A served family')")


def no_door(cell: str) -> str:
    """The sentence a cell (``cell``: "cell <name>") that says
    ``"routing": "engine"`` fails by where the engine built for it
    cannot say what it chose."""
    return (f"{cell}: its reference_check says \"routing\": "
            f"\"{serve_load.ROUTING}\", so its rows are compared under the "
            "engine's own routing choices, and the engine built for it gives no "
            "read_choices(cache): of the cache a _prefill or _decode call "
            "returned, the experts every routed layer chose in that call, "
            "int32 (routed layers, rows, k) (benchmarks/README.md, 'A served "
            "family')")


def choices_said(chunks: list, short: list, decodes: list, after: list,
                 tail: int) -> dict:
    """What ``probe_rows`` hands back of an engine's choices, from each
    call's (routed layers, n, k): the real ``chunks`` in order (the last
    of them the whole last chunk, of ``tail`` rows), the ``short`` last
    chunks (shortened by 1, 2, ...), each decode's lane 0 and the
    one-token call ``after`` each decode."""
    import numpy as np

    after = np.concatenate(after, 1)
    return {
        "rows": np.concatenate(chunks + decodes + [after[:, -1:]], 1),
        "after": after, "shortened_calls_chose_otherwise": sum(
            not np.array_equal(mine, chunks[-1][:, :tail - back])
            for back, mine in enumerate(short, 1))}


def probe_rows(eng, seq, positions: int, decode_steps: int):
    """The seeded probe ``seq`` through the engine's own jitted
    ``_prefill`` and ``_decode``, into slot 0 of its first cache shard;
    the engine must be idle. Returns the logits of the probe's last
    ``positions`` rows (the last first), the tokens the probe's last row
    and then ``decode_steps`` greedy decodes chose, and the logits of the
    row behind each decode, all float32 on the host; and, of an engine
    that can say what it chose, those choices (below; None of any other,
    which is read as before there was such a door).

    The programs return logits only here, so most of these calls are
    made only to read a row, and a call that a request would not make
    may not leave a trace: **every read-only call runs on a copy of the
    cache, and the cache it returns is dropped; the calls a request
    would make advance the real cache, once each.** No call is ever
    repeated on a cache that holds its result: rows a position would
    survive that (the same values written again), a recurrent state or a
    convolution's tail would not. The copy is a device copy of every
    leaf of ``shard.cache``, whatever the pytree holds, made before the
    call because the programs donate their cache; nothing of a family,
    a leaf's layout or a slot's axis is known here.

    *Prefill.* The whole chunks go in as a prompt's do. Before the last
    chunk goes in, each of the ``positions`` - 1 calls with ``length``
    shortened by 1, 2, ... (the chunk's tokens at its start into the
    same slot, returning the logits of its last real row) runs on a copy
    of the cache as it is then; the call with all of the chunk's tokens
    then runs on the real cache. Each copy is waited for and dropped
    before the next is made: the real cache and one copy are the most
    alive at once (``room_for_a_copy`` asks for that room first).
    *Decode.* Each greedy step advances the real cache;
    the one-token ``_prefill`` of the token it chose, at its position,
    which returns the logits the next step chooses from and attends to
    every row the decodes wrote, runs on a copy of the cache the decode
    returned; the next decode feeds that token to the real cache, which
    has not seen it.

    *The choices.* An engine MAY give ``read_choices(cache)``: of the
    cache a ``_prefill`` or ``_decode`` call returned, the experts every
    routed layer chose in that call, int32 (routed layers, rows, k), the
    bucket's rows of a ``_prefill`` (those at or behind ``length`` are
    ignored) or the lanes of a ``_decode`` first; a leaf the timed
    programs write, so nothing of what a cache holds is known here
    either. Every call's are read before its cache is dropped or handed
    on. Returned: ``rows`` (routed layers, ``length`` + ``decode_steps``
    + 1, k), what the calls that advanced the real cache chose at each
    row of the sequence (whole chunks, the last chunk, each decode's
    lane 0), its last row the last scratch call's (no real call fed that
    token); and ``after`` (routed layers, ``decode_steps``, k), what the
    one-token scratch call behind each decode chose at its row, which is
    the call that row's logits are read from and another program than
    the decode that later writes the row, so near a tie it may choose
    otherwise. A shortened last chunk computes rows the real call
    computes too, by the same program from the same cache, so its
    choices there are the real call's unless ``read_choices`` does not
    return what a call chose or a row's choices depend on the rows
    behind ``length`` (a scale taken over the whole bucket, say):
    ``shortened_calls_chose_otherwise`` counts the calls where they are
    not, and a cell compared under these choices holds it to 0."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    length = len(seq)
    starts = range(0, length, eng.prefill_chunk)
    tail = length - starts[-1]          # the last chunk's real tokens
    onehot = np.zeros(eng.max_batch, np.float32)
    onehot[0] = 1.0
    shard = eng.shards[0]
    read = getattr(eng, "read_choices", None)
    said = []       # a call's choices at its first n rows, call after call

    def say(cache, n):
        if read is not None:
            said.append(np.asarray(read(cache)[:, :n]))

    def prefill(tokens, pos, real=None, scratch=False):
        """One call: on the real cache, which it advances (dispatched,
        the logits left on the device), or, ``scratch``, on a copy of it,
        which is dropped, and then waited for, so that no second copy is
        made while this one is alive."""
        bucket = next(b for b in eng.buckets if b >= len(tokens))
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :len(tokens)] = tokens
        logits, cache = eng._prefill(
            eng.params, jax.tree_util.tree_map(jnp.copy, shard.cache)
            if scratch else shard.cache, padded, onehot,
            np.asarray([pos], np.int32), real or len(tokens), bucket=bucket)
        say(cache, real or len(tokens))
        if not scratch:
            shard.cache = cache
            return logits
        del cache
        return jax.block_until_ready(logits)

    def fetched(rows):
        return np.stack([np.asarray(x, np.float32) for x in rows])

    with eng._lock:
        if eng.num_active():
            raise RuntimeError("reference_check needs an idle engine")
        room_for_a_copy(shard.cache)
        for pos in starts[:-1]:
            prefill(seq[pos:pos + eng.prefill_chunk], pos)
        shortened = [
            prefill(seq[starts[-1]:], starts[-1], real=tail - back,
                    scratch=True) for back in range(1, positions)]
        got_prefill = fetched(
            [prefill(seq[starts[-1]:], starts[-1])] + shortened)
        chosen = [int(got_prefill[0].argmax())]
        lens = np.full(eng.max_batch, eng.max_seq - 1, np.int32)
        temps = np.zeros(eng.max_batch, np.float32)
        got_after = []
        for i in range(decode_steps):
            tokens = np.zeros(eng.max_batch, np.int32)
            tokens[0], lens[0] = chosen[-1], length + i
            toks, shard.cache, eng._rng = eng._decode(
                eng.params, shard.cache, tokens, lens, temps, eng._rng)
            say(shard.cache, 1)             # lane 0's row
            chosen.append(int(np.asarray(toks)[0]))
            got_after.append(prefill(chosen[-1:], length + i + 1,
                                     scratch=True))
        got_after = fetched(got_after)
    if read is None:
        return got_prefill, chosen, got_after, None
    # in the order made: the whole chunks, the shortened last chunks,
    # the last chunk, then a decode and its one-token scratch call by turns
    whole, rest = said[:len(starts) - 1], said[len(starts) - 1:]
    short, last, rest = rest[:positions - 1], rest[positions - 1], rest[
        positions:]
    return got_prefill, chosen, got_after, choices_said(
        whole + [last], short, rest[0::2], rest[1::2], tail)


def reference_readings(eng, family, seed: int, hp: dict, check: dict) -> dict:
    """``probe_rows`` of the engine against the family's float32
    ``reference_logits`` over the same tokens, read at many positions
    (``check``: the cell's ``reference_check`` block). The engine must
    be idle.

    *Prefill, through the cache.* ``length`` seeded tokens; the probe is
    at least one whole chunk plus ``positions``, so the compared chunk
    attends to rows (or starts from a state) an earlier call left.
    ``prefill_rel_rms`` holds the relative RMS of the logits of each of
    the probe's last ``positions`` rows against the reference's row, the
    probe's last position first.

    *Decode.* ``decode_steps`` greedy steps. The decode program returns
    tokens, not logits, so a step is judged twice: by how far the token
    it chose lies under the reference's largest logit at that position
    (``decode_choice_gap``), and through what it left in the cache: the
    logits of the row behind it (``after_decode_rel_rms``).

    What the engine is held to: a ``_prefill`` call leaves the slot as a
    sequence of ``start + length`` tokens, and whatever it wrote behind
    them is never read; a call with ``start`` 0 begins a sequence and
    reads nothing the slot held; a ``_decode`` leaves a live lane one
    token longer and may leave an idle lane's slot in any state; no call
    is ever repeated on a cache that holds its result (``probe_rows``).
    """
    import numpy as np

    length = check["length"]
    positions = check.get("positions", POSITIONS)
    decode_steps = check.get("decode_steps", DECODE_STEPS)
    seq = traffic.probe_sequence(seed, length, hp["vocab_size"])
    starts = range(0, length, eng.prefill_chunk)
    tail = length - starts[-1]          # the last chunk's real tokens
    if len(starts) < 2 or tail < positions:
        raise ValueError(
            f"reference_check: a probe of {length} tokens in chunks of "
            f"{eng.prefill_chunk} ends in a chunk of {tail} behind "
            f"{len(starts) - 1} whole one(s); it needs one whole chunk and "
            f"then {positions} positions or more in the last")
    # the one-token prefill behind the last decode computes a whole
    # bucket of rows, and one that ran past the end would be moved back
    # and read its row's logits from the wrong rows
    if length + decode_steps + eng.buckets[0] > eng.max_seq:
        raise ValueError(
            f"reference_check: {length} tokens, {decode_steps} decode steps "
            f"and a bucket of {eng.buckets[0]} rows do not fit the cache's "
            f"{eng.max_seq} rows")
    routed = serve_load.routed(check)
    if routed and getattr(eng, "read_choices", None) is None:
        raise RuntimeError(no_door(
            f"a cell of the configuration {hp.get('name')!r}"))
    t0 = time.perf_counter()
    got_prefill, chosen, got_after, said = probe_rows(eng, seq, positions,
                                                      decode_steps)
    engine_s = time.perf_counter() - t0
    engine_peak = holder.memory_peak_bytes()

    # the reference's rows: positions length - positions .. length +
    # decode_steps, the last of them the row behind the last decode
    full = np.concatenate([seq, np.asarray(chosen, np.int32)])
    rows = positions + decode_steps + 1
    at = positions - 1      # want[at] is the probe's last row, length - 1
    if routed:
        want, behind, routing = routed_rows(
            family, eng.params, full, hp, said, rows, decode_steps)
    else:
        want = np.asarray(family.reference_logits(
            eng.params, full, hp, last=rows), np.float32)
        behind, routing = want[at + 2:], {}

    def rel_rms(got, ref):
        return float(np.sqrt(np.mean((got - ref) ** 2))
                     / np.sqrt(np.mean(ref ** 2)))

    return {
        **routing,
        "prefill_rel_rms": [rel_rms(got_prefill[back], want[at - back])
                            for back in range(positions)],
        "after_decode_rel_rms": [rel_rms(got_after[i], behind[i])
                                 for i in range(decode_steps)],
        # how far under the reference's best logit each decode's token is
        "decode_choice_gap": [
            float(want[at + i + 1].max() - want[at + i + 1][chosen[i + 1]])
            for i in range(decode_steps)],
        "logit_rms": float(np.sqrt(np.mean(want ** 2))),
        "finite": bool(np.isfinite(got_prefill).all()
                       and np.isfinite(got_after).all()),
        "engine_s": engine_s,
        # the fullest chip's peak once the probe's calls are through,
        # the real cache and its copies among them
        "engine_memory_peak_bytes": engine_peak,
        "total_s": time.perf_counter() - t0,
    }


def routed_rows(family, params, full, hp: dict, said: dict, rows: int,
                decode_steps: int):
    """The reference's rows under the engine's own routing choices, for
    a cell that says ``"routing": "engine"``: the family's
    ``reference_routed(params, tokens, hp, choices, last=0) -> (logits,
    margin)``, its float32 pass in which every routed layer takes the
    experts in ``choices`` (routed layers, S, k) in place of its own
    top-k, weighted by its own scores of them, and ``margin`` (routed
    layers, S): how far the worst expert it was handed lies under its
    own k-th best, in units of the router's logits, 0 where the two sets
    are equal. One pass under ``said["rows"]`` (``probe_rows``: what the
    real cache went through) gives the last ``rows`` rows' logits, which
    the prefill's rows and the decodes' tokens are read against. The row
    behind a decode is read from a one-token call, which may choose
    otherwise than the decode that later writes that row: where it did
    (as sets, in any layer), one more pass with that row's choices
    replaced by the call's own gives that row (what lies behind it in
    that pass is not read). Returns those logits, the row behind each
    decode, and ``route_margin`` (the largest over the layers at every
    row of the sequence, then at every row read again),
    ``shortened_calls_chose_otherwise`` (``probe_rows``),
    ``routing_differs_share`` (of the (layer, row) pairs of the first
    pass, those whose sets differ) and ``rows_read_again``."""
    import numpy as np

    length = len(full) - decode_steps - 1
    want, margin = family.reference_routed(params, full, hp, said["rows"],
                                           last=rows)
    want, margin = np.asarray(want, np.float32), np.asarray(margin)
    behind = list(want[rows - decode_steps:])
    margins, again = list(margin.max(0)), 0
    for i in range(decode_steps - 1):   # the last row has the call's own
        row, own = length + i + 1, said["after"][:, i]
        if np.array_equal(np.sort(own, -1), np.sort(said["rows"][:, row], -1)):
            continue
        under = said["rows"].copy()
        under[:, row] = own
        logits, margin_again = family.reference_routed(params, full, hp,
                                                       under, last=rows)
        behind[i] = np.asarray(logits[rows - decode_steps + i], np.float32)
        margins.append(np.asarray(margin_again)[:, row].max())
        again += 1
    return want, behind, {
        "routing": serve_load.ROUTING,
        "shortened_calls_chose_otherwise":
            said["shortened_calls_chose_otherwise"],
        "route_margin": [float(x) for x in margins],
        "routing_differs_share": float((margin > 0).mean()),
        "rows_read_again": again}


# the reference's pass over a served request is padded (behind the
# tokens, which a causal model does not see) to a whole number of these,
# and its head taken over a whole number of HEAD_ROWS, so that a handful
# of programs serve every length a cell's traffic has
PAD_TOKENS, HEAD_ROWS = 512, 256


def served_readings(params, family, hp: dict, served) -> dict:
    """What the window itself produced, against the reference: for each
    of ``served`` (``[(prompt ids, served tokens)]``, greedy requests the
    engine finished with whatever else was alive in its lanes), one pass
    of the family's float32 ``reference_logits`` over the prompt with its
    served tokens, and for every served token how far its logit lies
    under the reference's largest at that position
    (``served_choice_gap``, request after request). A token the engine
    altered, took from another lane or chose from rows another request
    wrote reads a gap of whole logits; bf16 against float32 reads 0 but
    for near-ties."""
    import numpy as np

    t0 = time.perf_counter()
    gaps, by_request, agree = [], [], 0
    for prompt, tokens in served:
        n, first = len(tokens), len(prompt) - 1   # row `first` chose token 0
        seq = np.asarray(list(prompt) + list(tokens[:-1]), np.int32)
        total = -(-len(seq) // PAD_TOKENS) * PAD_TOKENS
        last = min(-(-(total - first) // HEAD_ROWS) * HEAD_ROWS, total)
        padded = np.zeros(total, np.int32)
        padded[:len(seq)] = seq
        want = family.reference_logits(params, padded, hp, last=last)
        at = first - (total - last)
        rows = np.asarray(want[at:at + n], np.float32)
        own = rows[np.arange(n), np.asarray(tokens)]
        gap = rows.max(axis=-1) - own
        gaps.extend(float(g) for g in gap)
        agree += int((gap == 0).sum())
        by_request.append([len(prompt), n, float(gap.max())])
    return {
        "served_choice_gap": gaps,
        # prompt tokens, served tokens, widest gap of each request read
        "served_by_request": by_request,
        "served_agree_share": agree / max(len(gaps), 1),
        "served_s": time.perf_counter() - t0,
    }


class BenchLLMServer(LLMServer):
    def __init__(self, llm_config: SeededLLMConfig):
        self._compiles = holder.CompileCounter()
        self._info = holder.device_info(llm_config.rehearse)
        super().__init__(llm_config)
        self._c = _new_counters()
        self._compiles_at_window = 0
        self._stats_at_window = None
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

    def reference_check(self, seed: int, hp: dict, check: dict) -> dict:
        """``reference_readings`` of this replica's engine, which must
        be idle: the cell's ``reference_check`` block says how long the
        probe is and at how many positions it is read."""
        return reference_readings(self.engine, spec.family_of(hp), seed, hp,
                                  check)

    def reads_choices(self) -> bool:
        """Whether the engine built here can say what it chose: what a
        cell that says ``"routing": "engine"`` asks before its window."""
        return getattr(self.engine, "read_choices", None) is not None

    def served_check(self, hp: dict, served: list) -> dict:
        """``served_readings`` of requests the window finished, once it
        has closed and ``memory_peak_bytes`` has been read."""
        return served_readings(self.engine.params, spec.family_of(hp), hp,
                               served)

    # -- the window -----------------------------------------------------
    def _engine_stats(self):
        """``engine_stats()`` of the system under test, None on a server
        from before it had one."""
        read = getattr(self, "engine_stats", None)
        return read() if read is not None else None

    def begin_window(self) -> None:
        self._c = _new_counters()
        self._compiles_at_window = self._compiles.count
        self._stats_at_window = self._engine_stats()

    def end_window(self) -> dict:
        c = self._c
        return {
            "samples": {**c, **holder.engine_deltas(
                self._stats_at_window, self._engine_stats())},
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
               "trace": trace, "scopes": self._program_scopes(),
               "cache_shapes": self._cache_shapes(),
               "peak": spec.peak_for(self._info["kind"], self.config.rehearse)}
        metrics, not_read = spec.evaluate(per_layer, ctx)
        return {
            "device": holder.traced_device_block(trace),
            "breakdown": holder.trace_reduce.breakdown(trace),
            "per_layer": metrics, "metrics_not_read": not_read,
        }

    def _program_scopes(self):
        """{program: {instruction: scope path}} of the engine's compiled
        programs (``decode``, ``prefill_<bucket>``), to join with the
        trace by (program, instruction); None on an engine from before
        ``compiled_programs()``. Compiles or loads each program once
        more, so only after the window."""
        programs = getattr(self.engine, "compiled_programs", None)
        if programs is None:
            return None
        from ray_tpu._private.jax_utils import scope_map  # as old as it

        return {k: scope_map(c) for k, c in programs().items()}

    def _cache_shapes(self) -> list:
        """The shape of every leaf of one cache shard as a trace names a
        result, ``bf16[24,8,8,2048,128]``: each once, in the pytree's
        order (keys and values share one; a latent cache, a recurrent
        state or a window's rows beside a full layer's bring theirs)."""
        import jax

        short = {"bfloat16": "bf16", "float16": "f16", "float32": "f32"}
        shapes = [f"{short.get(str(a.dtype), str(a.dtype))}"
                  f"[{','.join(str(d) for d in a.shape)}]"
                  for a in jax.tree_util.tree_leaves(
                      self.engine.shards[0].cache)]
        return list(dict.fromkeys(shapes))
