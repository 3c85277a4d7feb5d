"""Compile-only check of the device programs for a TPU v5e, without one.

    python tests/aot_compile_check.py

The installed libtpu compiles ahead of time for a topology it is only
told about: ``get_topology_desc("tpu", "v5e:2x2")`` gives four abstract
``TPU v5 lite`` devices, and lowering against ShapeDtypeStructs sharded
on them runs Mosaic and the TPU compiler for real. That costs no chip
time, so run it before every chip call. It catches what a CPU run
cannot: a kernel Mosaic refuses, a Pallas call GSPMD cannot partition,
a program that does not fit the chip's memory. It cannot say that a
program runs, is right or is fast.

Prints one line per program and exits non-zero if any failed to
compile; exits 77 when this libtpu cannot describe the topology. Each
line ends with the program's fingerprint (``fingerprint``): two trees
whose lines agree compile to the same instructions under the same
scopes, whatever their Python looks like. Words
on the command line keep to the programs whose line holds one of them
(``python tests/aot_compile_check.py latent``; ``grouped``: the grouped
SwiGLU of a chunk's experts at both routed configurations' widths).
tests/test_chip_path.py runs it in a subprocess with ``--quick``: the
same programs at the same widths, one layer and a shorter, smaller
batch, because each compile costs about a minute of CPU time.
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
import re
import sys
import time
from functools import partial

os.environ["JAX_PLATFORMS"] = "cpu"  # the process itself computes nowhere
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp


# an instruction of an optimized module's text: its result type with
# layout, its opcode, and the scope path jax gave the operation
_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT )?%\S+ = (.+?) ([a-z][a-z0-9-]*)\((?:.*?op_name=\"([^\"]*)\")?")


def signatures(text: str):
    """The optimized module ``text`` as a sorted list of computations,
    each the sorted list of its instructions' (opcode, result type with
    layout, ``op_name``): no instruction's or computation's name, no
    order inside a computation, no operand, no source location."""
    computations, current = [], None
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            current = []
        elif line == "}" and current is not None:
            computations.append(sorted(current))
            current = None
        elif current is not None:
            found = _INSTRUCTION.match(line)
            if found:
                shape, opcode, op_name = found.groups()
                current.append((opcode, shape, op_name or ""))
    return sorted(computations)


def fingerprint(text: str) -> str:
    """Twelve hex digits over ``signatures(text)``."""
    return hashlib.sha256(repr(signatures(text)).encode()).hexdigest()[:12]


def main() -> int:
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # whatever this libtpu raises when it cannot
        print(f"no TPU topology from this installation: {type(e).__name__}: {e}")
        return 77

    from ray_tpu import parallel
    from ray_tpu.models import llama
    from ray_tpu.ops import pallas_attention, pallas_ce
    from ray_tpu.parallel.train_step import state_shardings

    # this process's backend is the CPU, where the kernels would choose
    # to be interpreted; the programs below are for the TPU
    pallas_attention._interpret = lambda: False
    pallas_ce._interpret = lambda: False

    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    failures = 0

    only = [w for w in sys.argv[1:] if not w.startswith("--")]

    def check(name, lower, expect=(), forbid=None):
        nonlocal failures
        if only and not any(w in name for w in only):
            return
        t0 = time.perf_counter()
        try:
            compiled = lower().compile()
        except Exception as e:
            failures += 1
            print(f"FAIL {name}: {type(e).__name__}: {str(e)[:600]}")
            return
        text = compiled.as_text()
        missing = [n for n in expect if n not in text]
        unwanted = re.findall(forbid, text) if forbid else []
        mem = compiled.memory_analysis()
        if missing or unwanted:
            failures += 1
        print(
            f"{'FAIL' if missing or unwanted else 'ok  '} {name}: "
            f"{time.perf_counter() - t0:.1f}s, arguments "
            f"{mem.argument_size_in_bytes / 1e9:.2f} GB + temporaries "
            f"{mem.temp_size_in_bytes / 1e9:.2f} GB per device"
            + (f", kernels missing from the program: {missing}" if missing else "")
            + (f", instructions that must not be there: {unwanted}"
               if unwanted else "")
            + f", fingerprint {fingerprint(text)}"
        )

    quick = "--quick" in sys.argv[1:]
    cfg = dataclasses.replace(
        llama.LLAMA_BENCH, n_layers=1 if quick else 2,
        param_dtype=jnp.bfloat16, remat=True, attention_impl="flash",
    )
    B, S = (1, 512) if quick else (8, 2048)  # sequences per device
    flash_names = ("flash_attention_fwd", "flash_attention_bwd_dkv",
                   "flash_attention_bwd_dq")

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def flash(q, k, v, do):
        o, vjp = jax.vjp(
            lambda *a: pallas_attention.pallas_flash_attention(*a, causal=True),
            q, k, v,
        )
        return (o, *vjp(do))

    # the shapes one chip's kernels see in the two train cells
    # (internlm2-1.8b.train-2k, mistral-7b-v0.3.train-fsdp4); --quick
    # keeps to the one small shape of the other programs here. bwd_dkv
    # sums a GQA group inside the kernel: its results are kv-head
    # shaped, and no per-q-head partial is left for XLA to reduce
    shapes = ([(B, S, cfg.n_heads, cfg.n_kv_heads)] if quick
              else [(4, 2048, 16, 8), (2, 4096, 32, 8)])
    for b, s, h, kvh in shapes:
        q = sds((b, s, h, cfg.head_dim), jnp.bfloat16)
        kv = sds((b, s, kvh, cfg.head_dim), jnp.bfloat16)
        check(f"flash attention fwd+bwd, {b} x {s} x {h}/{kvh} heads, "
              "one device",
              lambda: jax.jit(flash).lower(q, kv, kv, q), flash_names,
              forbid=rf"flash_attention_bwd_dkv\S* = \(\w+\[{b},{h},{s},")

    def fused(x, w, t):
        loss, vjp = jax.vjp(lambda a, b: pallas_ce.fused_cross_entropy(a, b, t), x, w)
        return (loss, *vjp(jnp.ones_like(loss)))

    check(
        "fused cross entropy fwd+bwd, one device",
        lambda: jax.jit(fused).lower(
            sds((B * S, cfg.dim), jnp.bfloat16),
            sds((cfg.dim, cfg.vocab_size), jnp.bfloat16),
            sds((B * S,), jnp.int32),
        ),
        ("fused_ce_fwd", "fused_ce_bwd_dx", "fused_ce_bwd_dw"),
    )

    def train_step(n_devices):
        mesh = parallel.make_mesh(devices=topo.devices[:n_devices])
        opt = parallel.default_optimizer(1e-4)

        def init():
            params = llama.init_params(jax.random.PRNGKey(0), cfg)
            return parallel.TrainState(
                jnp.zeros((), jnp.int32), params, opt.init(params)
            )

        shardings, shapes = state_shardings(mesh, llama.param_specs(cfg), init)
        state = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            shapes, shardings,
        )
        batch = {"tokens": jax.ShapeDtypeStruct(
            (B * n_devices, S + 1), jnp.int32,
            sharding=parallel.batch_sharding(mesh),
        )}
        step = parallel.make_train_step(
            partial(llama.loss_fn, config=cfg), opt, mesh, shardings
        )
        return step.lower(state, batch)

    # two layers: one makes no scan, and nothing to bracket
    serve_cfg = dataclasses.replace(
        cfg, n_layers=2, remat=False, attention_impl="xla")
    abstract = partial(jax.tree.map, lambda a: sds(a.shape, a.dtype))
    cache = abstract(jax.eval_shape(
        partial(llama.init_kv_cache, serve_cfg, 8, 2048)))
    params = abstract(jax.eval_shape(
        partial(llama.init_params, config=serve_cfg), jax.random.PRNGKey(0)))
    prefill_chunk = partial(lower_chunk, sds, llama, serve_cfg, params, cache)
    decode_step = partial(lower_decode, sds, llama, serve_cfg, params, cache)

    # the cache is updated in place: no instruction copies a whole leaf
    # of the shard (layout assignment once bracketed the layer scan with
    # two, for chunks of 128 rows and more). The three buckets of a v5e's
    # derived chunk: 64 rows run without forward_with_cache's barrier,
    # 128 and 256 with it, all reading the whole slot, the top one of the
    # engine's read windows. Then the other window at 8 x 2048, the
    # first 1024 rows, where the layer reads fewer rows than the carried
    # stack holds, for the whole chunk, and the decode step at both; then
    # the decode over a pair of shards, two donated caches in and two out,
    # at the top window, the one the engine runs it at
    no_shard_copy = no_copy_of(cache["k"])
    for rows in (64, 128, 256):
        check(f"prefill chunk of {rows} rows into one slot of 8 x 2048, "
              "one device", partial(prefill_chunk, rows), forbid=no_shard_copy)
    check("prefill chunk of 256 rows reading 1024 of 8 x 2048, one device",
          partial(prefill_chunk, 256, 1024), forbid=no_shard_copy)
    for window in (1024, 2048):
        check(f"decode step of 8 lanes reading {window} of 8 x 2048, "
              "one device", partial(decode_step, 8, window),
              forbid=no_shard_copy)
    check("decode step of 2 x 8 lanes reading 2048 of a pair of 8 x 2048, "
          "one device", partial(decode_step, 8, 2048, 2), forbid=no_shard_copy)

    grouped_swiglu(check, sds, quick)
    if not quick:
        latent_chunks(check, sds)
        indexed_latent_chunks(check, sds)
        window_pair(check, sds)
        state_pair(check, sds)
        parallel_chunks(check, sds)

    for n in (1, 4):
        check(f"train step, LLAMA_BENCH width x {cfg.n_layers} layer(s), "
              f"{B}x{S} per device, {n} device(s)",
              partial(train_step, n), flash_names)
    return 1 if failures else 0


def lower_chunk(sds, model, cfg, params, cache, rows, window=None):
    """What the engine's prefill program does with a chunk of one
    sequence, lowered: its ``rows`` rows into one slot of a donated cache
    shard, attention over the first ``window`` rows of the slot."""
    def chunk(params, cache, tokens, start, slot, at):
        return model.forward_with_cache(
            params, tokens, cache, start, cfg, slot=slot, logits_at=at,
            rows=window)

    return jax.jit(chunk, donate_argnums=(1,)).lower(
        params, cache, sds((1, rows), jnp.int32), sds((1,), jnp.int32),
        sds((), jnp.int32), sds((1,), jnp.int32))


def lower_decode(sds, model, cfg, params, cache, lanes, window, shards=1):
    """The engine's decode program without its sampling, lowered: one
    row a lane, every lane of the shard; with ``shards`` of 2, of a pair
    of shards in one call, both caches donated."""
    def step(params, cache, tokens, lengths):
        return model.forward_with_cache(
            params, tokens[:, None], cache, lengths, cfg, rows=window)

    if shards > 1:
        cache, lanes = (cache,) * shards, shards * lanes
    return jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, sds((lanes,), jnp.int32), sds((lanes,), jnp.int32))


def no_copy_of(*leaves) -> str:
    """The pattern of an instruction that copies the whole of one of
    the cache's ``leaves``."""
    shapes = "|".join(",".join(str(d) for d in a.shape) for a in leaves)
    return rf"\[(?:{shapes})\]\S* copy\("


def cell_config(name: str, model):
    """-> (configuration, lanes, max_seq, chunk) of the serving cell
    ``name``: its published widths, its cell's lanes and cache length,
    the chunk a v5e's engine derives."""
    from benchmarks import spec
    from ray_tpu.llm._internal.engine import derived_prefill_chunk

    cell = spec.load_cell(name, False)
    cfg = spec.family_of(cell["hp"]).model_config(cell["hp"])
    lanes, max_seq = (cell["serve"][k] for k in ("max_batch_size",
                                                 "max_seq_len"))
    chunk = derived_prefill_chunk(
        "TPU v5 lite", 2, max_seq,
        **getattr(model, "chunk_terms", lambda *_: {})(cfg, max_seq))
    return cfg, lanes, max_seq, chunk


def serving_cell(sds, name: str, model):
    """-> (configuration, parameters, cache, lanes, max_seq, chunk) of
    the serving cell ``name`` as a v5e's engine would hold them
    (``cell_config``), the arrays as shapes."""
    cfg, lanes, max_seq, chunk = cell_config(name, model)
    abstract = partial(jax.tree.map, lambda a: sds(a.shape, a.dtype))
    params = abstract(jax.eval_shape(
        partial(model.init_params, config=cfg), jax.random.PRNGKey(0)))
    cache = abstract(jax.eval_shape(
        partial(model.init_cache, cfg, lanes, max_seq, chunk)))
    return cfg, params, cache, lanes, max_seq, chunk


def grouped_swiglu(check, sds, quick):
    """The grouped SwiGLU of a chunk's experts
    (``ops/pallas_grouped_matmul.py``) alone, at the published widths of
    the three configurations that reach ``moe.expert_ffn`` and the
    assignments one call of it takes in every chunk bucket of their
    cells (``moe._slab`` of rows x top-8: all of them where every expert
    is held, a slab where a share is; ``--quick``: the smallest bucket
    of each): 64 experts of 2304 x 896, whose matrices go into VMEM
    whole, and 8 held experts of 7680 x 2048 and 16 of 4096 x 4096,
    whose columns are tiled; two layers' stacks, the layer a traced
    index. Mosaic's answer on the tile shapes and on VMEM."""
    from ray_tpu.models import latent_moe, parallel_moe, window_moe
    from ray_tpu.ops import moe

    for name, model in (("mellum2-12b-a2.5b.serve-ide-mix", window_moe),
                        ("openpangu-ultra-moe-718b.serve-longdoc",
                         latent_moe),
                        ("command-a-plus-05-2026.serve-rag", parallel_moe)):
        cfg, _, _, chunk = cell_config(name, model)
        c = cfg.moe
        E = c.n_experts if c.held is None else len(c.held)
        up, down = (sds((2, E, *shape), cfg.dtype) for shape in (
            (c.d_model, c.d_ff), (c.d_ff, c.d_model)))
        for rows in (chunk // 4, chunk // 2, chunk)[:1 if quick else 3]:
            taken = moe._slab(c, rows * c.k)
            check(f"grouped SwiGLU of {taken} of a {rows}-row chunk's "
                  f"{rows * c.k} assignments, {E} experts of {c.d_model} x "
                  f"{c.d_ff} ({name.rsplit('.', 1)[0]}), one device",
                  lambda taken=taken: jax.jit(moe.expert_ffn).lower(
                      sds((taken, c.d_model), cfg.dtype), up, up, down,
                      sds((E,), jnp.int32), sds((), jnp.int32)),
                  expect=("grouped_swiglu_gate_up", "grouped_swiglu_down"),
                  forbid=r"ragged-dot|ragged_dot")


def every_assignment(cfg, rows: int) -> str:
    """The pattern of a float32 tensor of every assignment of a
    ``rows``-row chunk x the width (the product of the grouped matmul
    over all of T x k, its zeroing, its gather back), which no program
    of a configuration that holds a share of the experts has
    (``moe._held_slabs``: a slab's at the most); one that matches
    nothing where every expert is held."""
    from ray_tpu.ops import moe

    c = cfg.moe
    if moe._slab(c, rows * c.k) == rows * c.k:
        return r"(?!)"
    return rf"f32\[{rows * c.k},{c.d_model}\]"


def latent_chunks(check, sds):
    """The programs of ``openpangu-ultra-moe-718b.serve-longdoc`` at its
    published widths and the cell's 16 x 16 384 cache: the three buckets
    of the chunk a v5e's engine derives (1024 rows) reading the whole
    slot, the whole chunk at the other read window, and the decode step
    at both. None may copy a leaf of the shard (as one 576-wide leaf the
    cache was bracketed by two transposing copies of 3 GB, PERF.md
    section 6, PR 48), each chunk holds the prefill form's kernel
    (``ops/pallas_latent_attention.py``), and the line says what
    temporaries a call of so many rows takes beside the 8.3 GB of
    weights and cache."""
    from ray_tpu.models import latent_moe

    cfg, params, cache, lanes, max_seq, chunk = serving_cell(
        sds, "openpangu-ultra-moe-718b.serve-longdoc", latent_moe)
    no_leaf_copy = no_copy_of(cache["latent"], cache["rope_key"])
    # nor hold a float32 score of all the heads (the block loop's was
    # heads x tile x block; the kernel's is a head's, in VMEM)
    forbidden = rf"{no_leaf_copy}|f32\[{cfg.n_heads},\d+,\d+\]"
    for rows, window in ((chunk // 4, max_seq), (chunk // 2, max_seq),
                         (chunk, max_seq), (chunk, max_seq // 2)):
        check(f"latent prefill chunk of {rows} rows reading {window} of "
              f"{lanes} x {max_seq}, published widths, one device",
              partial(lower_chunk, sds, latent_moe, cfg, params, cache, rows,
                      window),
              expect=("latent_attention_prefill", "grouped_swiglu_gate_up"),
              forbid=rf"{forbidden}|{every_assignment(cfg, rows)}")
    for window in (max_seq // 2, max_seq):
        check(f"latent decode step of {lanes} lanes reading {window} of "
              f"{lanes} x {max_seq}, published widths, one device",
              partial(lower_decode, sds, latent_moe, cfg, params, cache,
                      lanes, window), expect=("latent_attention_decode",),
              forbid=rf"{no_leaf_copy}|f32\[{lanes},{cfg.n_heads},\d{{3,}}\]")


def indexed_latent_chunks(check, sds):
    """The programs of ``deepseek-v3.2-exp.serve-longctx`` at its
    published widths and the cell's 8 x 25 600 cache: the three buckets
    of the chunk a v5e's engine derives (1024 rows) reading the whole
    slot, the whole chunk at the other read window, and the decode step
    at both. None may copy a leaf of the shard; each chunk holds the
    indexer's score kernel (``ops/pallas_index_score.py``), the expanded
    form's kernel (under the selection's mask) and the grouped SwiGLU,
    and no float32 score of index heads x chunk rows x cache rows; a
    decode holds no float32 score of every latent row a head."""
    from ray_tpu.models import latent_moe

    cfg, params, cache, lanes, max_seq, chunk = serving_cell(
        sds, "deepseek-v3.2-exp.serve-longctx", latent_moe)
    no_leaf_copy = no_copy_of(cache["latent"], cache["rope_key"],
                              cache["index_key"])
    for rows, window in ((chunk // 4, max_seq), (chunk // 2, max_seq),
                         (chunk, max_seq), (chunk, max_seq // 2)):
        check(f"indexed latent prefill chunk of {rows} rows reading {window} "
              f"of {lanes} x {max_seq}, published widths, one device",
              partial(lower_chunk, sds, latent_moe, cfg, params, cache, rows,
                      window),
              expect=("index_score", "latent_attention_prefill",
                      "grouped_swiglu_gate_up"),
              forbid=rf"{no_leaf_copy}|f32\[\d*,?{cfg.index_heads},{rows},\d+\]"
                     rf"|{every_assignment(cfg, rows)}")
    for window in (max_seq // 2, max_seq):
        check(f"indexed latent decode step of {lanes} lanes reading {window} "
              f"of {lanes} x {max_seq}, published widths, one device",
              partial(lower_decode, sds, latent_moe, cfg, params, cache,
                      lanes, window),
              forbid=rf"{no_leaf_copy}|f32\[{lanes},{cfg.n_heads},{window}\]")


def tiled_chunks(check, sds, label, model, cfg, params, cache, lanes,
                 max_seq, chunk) -> str:
    """The chunk programs of a family that serves through
    ``window_moe.cached_periods`` (the two smaller buckets at the top
    read window, the whole chunk at both): each holds the kernels of
    ``ops/pallas_chunk_attention.py`` and of the grouped SwiGLU, no
    float32 score of heads x chunk rows x cache rows, no float32 product
    of every assignment where a share of the experts is held, and no
    copy of a whole stack of rows or of rings -> the pattern of such a
    copy."""
    no_stack_copy = no_copy_of(cache["full"]["k"], cache["ring"]["k"])
    ring = cache["ring"]["k"].shape[3] - 8
    for rows, window in ((chunk // 4, max_seq), (chunk // 2, max_seq),
                         (chunk, max_seq // 2), (chunk, max_seq)):
        # a score of a group's heads (or of all) x the call's rows x a
        # layer's rows read, in any order of the leading axes
        score = rf"f32\[\d[\d,]*,{rows},(?:{ring}|{ring + 8}|{window})\]"
        check(f"{label} prefill chunk of {rows} rows reading {window} of "
              f"{lanes} x {max_seq}, published widths, one device",
              partial(lower_chunk, sds, model, cfg, params, cache, rows,
                      window),
              expect=("chunk_attention", "grouped_swiglu_gate_up"),
              forbid=rf"{no_stack_copy}|ragged-dot|{score}|"
                     rf"{every_assignment(cfg, rows)}")
    return no_stack_copy


def window_pair(check, sds):
    """The programs of ``mellum2-12b-a2.5b.serve-ide-mix`` at its
    published widths, its two periods of layers (one would make no scan,
    and nothing to bracket) and its 16 x 8192 cache: the whole chunk at
    both read windows and the two smaller buckets, the decode step and
    the decode step over a pair of shards. A chunk program holds the
    kernel of ``ops/pallas_chunk_attention.py`` (8 query heads a
    key/value head) and no float32 score of heads x chunk rows x cache
    rows; none may copy a whole stack of the full layers' rows or of the
    rings."""
    from ray_tpu.models import window_moe

    cfg, params, cache, lanes, max_seq, chunk = serving_cell(
        sds, "mellum2-12b-a2.5b.serve-ide-mix", window_moe)
    no_stack_copy = tiled_chunks(check, sds, "window_moe", window_moe, cfg,
                                 params, cache, lanes, max_seq, chunk)
    check(f"window_moe decode step of {lanes} lanes reading {max_seq} of "
          f"{lanes} x {max_seq}, published widths, one device",
          partial(lower_decode, sds, window_moe, cfg, params, cache, lanes,
                  max_seq),
          forbid=rf"{no_stack_copy}|ragged-dot|grouped_swiglu")
    check(f"window_moe decode step of 2 x {lanes} lanes reading {max_seq} "
          f"of a pair of {lanes} x {max_seq}, published widths, one device",
          partial(lower_decode, sds, window_moe, cfg, params, cache, lanes,
                  max_seq, 2),
          forbid=rf"{no_stack_copy}|ragged-dot|grouped_swiglu")


def parallel_chunks(check, sds):
    """The programs of ``command-a-plus-05-2026.serve-rag`` at its
    published widths (128 query heads over 8 key/value heads, 16 held
    experts of 4096 x 4096, a 16 x 16 384 cache): the whole chunk at
    both read windows, the two smaller buckets, the decode step at both.
    A chunk program holds the kernel of ``ops/pallas_chunk_attention.py``
    and no float32 score of heads x chunk rows x cache rows; none copies
    a whole stack of rows or of rings."""
    from ray_tpu.models import parallel_moe

    cfg, params, cache, lanes, max_seq, chunk = serving_cell(
        sds, "command-a-plus-05-2026.serve-rag", parallel_moe)
    no_stack_copy = tiled_chunks(check, sds, "parallel_moe", parallel_moe,
                                 cfg, params, cache, lanes, max_seq, chunk)
    for window in (max_seq // 2, max_seq):
        check(f"parallel_moe decode step of {lanes} lanes reading {window} of "
              f"{lanes} x {max_seq}, published widths, one device",
              partial(lower_decode, sds, parallel_moe, cfg, params, cache,
                      lanes, window),
              forbid=rf"{no_stack_copy}|ragged-dot|grouped_swiglu")


def state_pair(check, sds):
    """The programs of ``ai21-jamba2-3b.serve-reason`` at its published
    widths, all 28 layers and the cell's 128 x 4096 cache: the three
    buckets of the chunk a v5e's engine derives reading the whole slot,
    the whole chunk at the other read window, and the decode step at
    both. None may copy a whole leaf of the shard (the states, the
    tails, the keys or the values), each chunk holds the scan's kernel
    (``ops/selective_scan.py``), and the line says what temporaries a
    call takes beside the 7.8 GB of weights and cache."""
    from ray_tpu.models import hybrid_ssm
    from ray_tpu.ops import selective_scan

    selective_scan._interpret = lambda: False
    cfg, params, cache, lanes, max_seq, chunk = serving_cell(
        sds, "ai21-jamba2-3b.serve-reason", hybrid_ssm)
    no_leaf_copy = no_copy_of(cache["state"], cache["tail"], cache["k"])
    for rows, window in ((chunk // 4, max_seq), (chunk // 2, max_seq),
                         (chunk, max_seq), (chunk, max_seq // 2)):
        check(f"state prefill chunk of {rows} rows reading {window} of "
              f"{lanes} x {max_seq}, published widths, one device",
              partial(lower_chunk, sds, hybrid_ssm, cfg, params, cache, rows,
                      window),
              expect=("selective_scan_chunk",), forbid=no_leaf_copy)
    for window in (max_seq // 2, max_seq):
        check(f"state decode step of {lanes} lanes reading {window} of "
              f"{lanes} x {max_seq}, published widths, one device",
              partial(lower_decode, sds, hybrid_ssm, cfg, params, cache,
                      lanes, window), forbid=no_leaf_copy)


if __name__ == "__main__":
    sys.exit(main())
