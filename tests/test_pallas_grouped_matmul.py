"""``ops/pallas_grouped_matmul.py``: the grouped SwiGLU's kernel in
interpret mode at small shapes it can tile (rows of 128 and 256 wide,
row tiles of 32), against a plain float32 loop over the groups written
here, through ``moe.expert_ffn`` and ``moe.moe_ffn_dropless`` as the
models call it; and which of the layer's paths a served model's programs
hold at its published widths."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ray_tpu.ops import moe  # noqa: E402
from ray_tpu.ops import pallas_grouped_matmul as kernel  # noqa: E402

D, F, E, TILE = 256, 128, 6, 32


@pytest.fixture(autouse=True)
def small_tiles(monkeypatch):
    """Row tiles of 32, and weight blocks of 64 KiB at the most: gate and
    up (256 x 128 float32) go whole, down (128 x 256) in two column
    tiles of 128, so the grid's outer axis is walked too."""
    monkeypatch.setattr(kernel, "_ROW_TILE", TILE)
    monkeypatch.setattr(kernel, "_WEIGHT_BLOCK_BYTES", 2**17)


def weights(seed, experts=E, dtype=jnp.float32, layers=None):
    lead = (experts,) if layers is None else (layers, experts)
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(
        (jax.random.normal(key, lead + shape) / shape[0] ** 0.5).astype(dtype)
        for key, shape in zip(k, ((D, F), (D, F), (F, D))))


def rows(seed, n, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(100 + seed), (n, D)).astype(
        dtype)


def loop_over_groups(xs, w_gate, w_up, w_down, sizes):
    """Each group's rows through its expert, one group at a time, in
    float32; 0 behind the last group."""
    f32 = lambda a: np.asarray(a, np.float32)
    xs, out, at = f32(xs), np.zeros((xs.shape[0], w_down.shape[-1]),
                                    np.float32), 0
    for e, n in enumerate(sizes):
        x = xs[at:at + n]
        gate, up = x @ f32(w_gate[e]), x @ f32(w_up[e])
        out[at:at + n] = (gate / (1 + np.exp(-gate)) * up) @ f32(w_down[e])
        at += n
    return out


# name -> (rows of the call, rows of each of the 6 experts)
GROUPS = {
    "uneven": (256, [100, 1, 30, 63, 2, 60]),
    "empty_at_the_front": (128, [0, 0, 50, 40, 30, 8]),
    "empty_in_the_middle": (128, [20, 0, 0, 70, 0, 38]),
    "empty_at_the_end": (128, [64, 60, 4, 0, 0, 0]),
    "every_row_at_one_expert": (128, [0, 0, 0, 128, 0, 0]),
    "edges_on_the_tiles": (192, [32, 64, 0, 32, 32, 32]),
    "edges_inside_a_tile": (96, [5, 7, 9, 11, 13, 51]),
    "several_groups_in_one_tile": (64, [3, 4, 5, 6, 7, 39]),
    "one_row": (32, [0, 0, 1, 0, 0, 0]),
    "rows_behind_the_last_group": (256, [10, 0, 37, 3, 0, 20]),
    "no_row_at_all": (64, [0, 0, 0, 0, 0, 0]),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("name", list(GROUPS))
def test_grouped_swiglu_equals_a_loop_over_the_groups(name, dtype):
    """Through ``moe.expert_ffn``, which has to take the kernel: every
    expert's rows come out as the loop's, whatever the groups' edges;
    what comes out behind the last group is nobody's (the interpreter
    leaves NaN where the kernel wrote nothing) and reaches no row that
    is somebody's."""
    n, sizes = GROUPS[name]
    xs, (w_gate, w_up, w_down) = rows(1, n, dtype), weights(2, dtype=dtype)
    assert kernel.untileable(xs, w_gate, w_down) is None
    got = np.asarray(jax.jit(moe.expert_ffn)(
        xs, w_gate, w_up, w_down, jnp.asarray(sizes, jnp.int32)))
    assert got.shape == (n, D) and got.dtype == np.float32
    want, held = loop_over_groups(xs, w_gate, w_up, w_down, sizes), sum(sizes)
    np.testing.assert_allclose(
        got[:held], want[:held], atol=2e-5 if dtype == jnp.float32 else 0.05)
    assert not np.isnan(got[:held]).any()


@pytest.mark.parametrize("n", [TILE - 1, TILE + 8, 100])
def test_rows_that_are_no_multiple_of_the_tile_go_to_ragged_dot(n):
    """The kernel refuses them, and ``expert_ffn`` computes them as it
    did before there was a kernel."""
    xs, (w_gate, w_up, w_down) = rows(3, n), weights(4)
    assert "row tile" in kernel.untileable(xs, w_gate, w_down)
    with pytest.raises(NotImplementedError):
        kernel.grouped_swiglu(xs, w_gate, w_up, w_down, None)
    sizes = [n - 20, 0, 5, 5, 10, 0]
    text = str(jax.make_jaxpr(moe.expert_ffn)(
        xs, w_gate, w_up, w_down, jnp.asarray(sizes, jnp.int32)))
    assert "ragged_dot" in text and "pallas_call" not in text
    got = moe.expert_ffn(xs, w_gate, w_up, w_down,
                         jnp.asarray(sizes, jnp.int32))
    np.testing.assert_allclose(
        np.asarray(got), loop_over_groups(xs, w_gate, w_up, w_down, sizes),
        atol=2e-5)


@pytest.mark.parametrize("why, change", [
    ("lanes", lambda xs, g, d: (xs[:, :96], g[:, :96], d[:, :, :96])),
    ("lanes", lambda xs, g, d: (xs, g[:, :, :64], d[:, :64])),
    ("beside weights", lambda xs, g, d: (xs.astype(jnp.bfloat16), g, d)),
    ("do not fit", lambda xs, g, d: (xs, g, d[:, :, :128])),
])
def test_shapes_the_kernel_refuses(why, change):
    xs, (w_gate, _, w_down) = rows(5, 64), weights(6)
    assert why in kernel.untileable(*change(xs, w_gate, w_down))


@pytest.mark.parametrize("layer", [0, 2, 4], ids=["first", "middle", "last"])
def test_a_layer_of_the_stack_equals_its_slice(layer):
    """``layer`` is an index of the weights' block: the stacked
    (L, E, D, F) goes in whole and the result is that layer's, for a
    traced index."""
    stack = weights(7, layers=5)
    xs, sizes = rows(8, 128), jnp.asarray([30, 0, 50, 1, 40, 7], jnp.int32)
    got = jax.jit(moe.expert_ffn)(xs, *stack, sizes, jnp.int32(layer))
    text = str(jax.make_jaxpr(moe.expert_ffn)(xs, *stack, sizes,
                                              jnp.int32(layer)))
    assert "pallas_call" in text and "dynamic_slice" not in text
    want = moe.expert_ffn(xs, *(w[layer] for w in stack), sizes)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_allclose(
        np.asarray(got),
        loop_over_groups(xs, *(w[layer] for w in stack), np.asarray(sizes)),
        atol=2e-5)


# ------------------------------------------ through the dropless layer
HELD = moe.MoEConfig(d_model=D, d_ff=F, n_experts=16, k=4, scoring="sigmoid",
                     routed_scale=2.5, held=(4, 5, 6, 7, 12, 13))
ALL = moe.MoEConfig(d_model=D, d_ff=F, n_experts=E, k=2)


def layer_params(seed, config):
    held = config.n_experts if config.held is None else len(config.held)
    w_gate, w_up, w_down = weights(seed, experts=held)
    router = jax.random.normal(jax.random.PRNGKey(seed + 50),
                               (D, config.n_experts)) / D ** 0.5
    return {"router": router, "w_gate": w_gate, "w_up": w_up,
            "w_down": w_down}


def per_token_loop(params, x, config):
    """Every token through each of its chosen experts that is held, one
    at a time."""
    weights_, experts = moe.route_top_k(x, params["router"], config)
    held = list(range(config.n_experts) if config.held is None
                else config.held)
    x, out = np.asarray(x, np.float64), np.zeros(x.shape, np.float64)
    w = {k: np.asarray(v, np.float64) for k, v in params.items()}
    for t in range(x.shape[0]):
        for weight, e in zip(np.asarray(weights_[t]), np.asarray(experts[t])):
            if int(e) not in held:
                continue
            at = held.index(int(e))
            gate, up = x[t] @ w["w_gate"][at], x[t] @ w["w_up"][at]
            out[t] += weight * (gate / (1 + np.exp(-gate)) * up) @ w[
                "w_down"][at]
    return out


def holds_the_kernel(f, *args) -> bool:
    return "grouped_swiglu" in str(jax.make_jaxpr(f)(*args))


def test_a_held_share_leaves_zeros_behind_the_last_group():
    """6 experts of 16 held: most assignments sort behind the last
    group, where the kernel writes nothing (NaN under the interpreter);
    none of it reaches a row's sum, and a row that chose no held expert
    comes out 0."""
    params = layer_params(9, HELD)
    x = jax.random.normal(jax.random.PRNGKey(10), (128, D))
    f = lambda p, x: moe.moe_ffn_dropless(p, x, HELD)
    assert holds_the_kernel(f, params, x)
    out, counts = jax.jit(f)(params, x)
    want = per_token_loop(params, x, HELD)
    assert not np.isnan(np.asarray(out)).any()
    np.testing.assert_allclose(np.asarray(out), want, atol=5e-5)
    nobody = np.abs(want).max(-1) == 0
    assert nobody.any() and np.abs(np.asarray(out)[nobody]).max() == 0
    assert 0 < int(counts[0]) < 128 * 4 * 0.6 and int(counts[2]) == 6


def test_the_kernel_under_a_mesh_runs_in_every_shard_of_the_rows():
    """Inside the ``shard_map`` of ``moe_ffn_dropless``: four shards of
    128 rows, each sorting and multiplying its own against the whole of
    the experts."""
    from jax.sharding import Mesh

    params = layer_params(11, ALL)
    x = jax.random.normal(jax.random.PRNGKey(12), (512, D))
    f = lambda p, x: moe.moe_ffn_dropless(p, x, ALL)
    want, counts = f(params, x)
    np.testing.assert_allclose(np.asarray(want),
                               per_token_loop(params, x, ALL), atol=5e-5)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "fsdp"))
    with jax.sharding.set_mesh(mesh):
        assert holds_the_kernel(f, params, x)
        got, split = jax.jit(f)(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    assert int(split[0]) == int(counts[0]) == 1024


# --------------------------- what a served model's programs hold
def programs_text(cell_name, model, buckets_of):
    """The jaxprs of a serving cell's chunk programs (a bucket each) and
    of its decode step at the published widths, traced on shapes."""
    import aot_compile_check as aot

    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype)
    cfg, params, cache, lanes, max_seq, chunk = aot.serving_cell(
        sds, cell_name, model)

    def prefill(params, cache, tokens, start, slot, at):
        return model.forward_with_cache(
            params, tokens, cache, start, cfg, slot=slot, logits_at=at,
            rows=max_seq)

    def decode(params, cache, tokens, lengths):
        return model.forward_with_cache(
            params, tokens[:, None], cache, lengths, cfg, rows=max_seq)

    chunks = {
        rows: str(jax.make_jaxpr(prefill)(
            params, cache, sds((1, rows), jnp.int32), sds((1,), jnp.int32),
            sds((), jnp.int32), sds((1,), jnp.int32)))
        for rows in buckets_of(chunk)}
    step = str(jax.make_jaxpr(decode)(
        params, cache, sds((lanes,), jnp.int32), sds((lanes,), jnp.int32)))
    return chunks, step


def test_mellum2s_chunk_programs_hold_the_kernel_and_its_decode_neither():
    """At the published widths (2304 wide, 64 experts of 896, top-8) and
    the cell's sizes: every bucket of the chunk a v5e's engine derives
    multiplies its experts in the kernel and holds no ``ragged_dot``; a
    decode call goes through every expert and holds neither."""
    from ray_tpu.models import window_moe

    chunks, step = programs_text(
        "mellum2-12b-a2.5b.serve-ide-mix", window_moe,
        lambda chunk: (chunk // 4, chunk // 2, chunk))
    assert sorted(chunks) == [512, 1024, 2048]
    for rows, text in chunks.items():
        assert "grouped_swiglu_gate_up" in text, rows
        assert "grouped_swiglu_down" in text, rows
        assert "ragged_dot" not in text, rows
    assert "pallas_call" not in step and "ragged_dot" not in step
