"""``ops/pallas_latent_attention.py``: the prefill form's kernel in
interpret mode at small shapes it can tile (2 heads of 128 + 64 / 128,
latent rows of 128 or 512, float32 and bfloat16), against the
whole-matrix arithmetic (``attend_whole`` of ``test_latent_moe.py``) and
against ``latent_moe``'s ``jax.numpy`` block loop, through
``latent_moe.attend_expanded`` as the model calls it; and the decode
form's kernel (16 heads, blocks of 128 rows) against ``latent_moe``'s
``jax.numpy`` loop over the same cache, through
``latent_moe.attend_absorbed``."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from test_latent_moe import attend_whole, rel_rms  # noqa: E402
from ray_tpu.models import decoder  # noqa: E402
from ray_tpu.models import latent_moe as lm  # noqa: E402
from ray_tpu.ops import pallas_latent_attention as kernel  # noqa: E402

TILEABLE = dataclasses.replace(
    lm.LATENT_MOE_TINY, n_heads=2, nope_dim=128, rope_dim=64, v_dim=128,
    kv_rank=128, dtype=jnp.float32, param_dtype=jnp.float32)
LAYERS, LANES, CACHE_ROWS = 2, 3, 512


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Blocks and tiles of 128 rows, so that a cache of 512 is four
    blocks and a chunk of 256 two tiles."""
    monkeypatch.setattr(kernel, "_BLOCK", 128)
    monkeypatch.setattr(kernel, "_TILE", 128)
    monkeypatch.setattr(kernel, "_DECODE_BLOCK", 128)
    monkeypatch.setattr(kernel, "_DECODE_TILE", 128)


def inputs(c, seed, T, start):
    """The cache's two stacks filled with noise, queries of T rows a
    sequence at ``start`` (one entry a sequence) and a layer's two
    up-projections."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    B, H = len(start), c.n_heads
    n = lambda k, *shape: jax.random.normal(k, shape, jnp.float32)
    stack = (n(keys[0], LAYERS, LANES, CACHE_ROWS, c.kv_rank).astype(c.dtype),
             n(keys[1], LAYERS, LANES, c.rope_dim, CACHE_ROWS).astype(c.dtype))
    layer = {"wuk": (n(keys[2], c.kv_rank, H, c.nope_dim) / 6).astype(c.dtype),
             "wuv": (n(keys[3], c.kv_rank, H, c.v_dim) / 6).astype(c.dtype)}
    q_nope = n(keys[4], B, T, H, c.nope_dim).astype(c.dtype)
    q_rope = n(keys[5], B, T, H, c.rope_dim).astype(c.dtype)
    return stack, layer, q_nope, q_rope, jnp.asarray(start, jnp.int32)


def by_head(q_nope, q_rope):
    """(B, T, H, .) -> (B, H, T, .), as the kernel takes its queries."""
    return q_nope.transpose(0, 2, 1, 3), q_rope.transpose(0, 2, 1, 3)


def whole(c, q_nope, q_rope, stack, index, first, start, layer):
    """``attend_whole`` in float32 over the sequences' rows of the
    stacks, as one array of 576-wide rows."""
    B, T = q_nope.shape[:2]
    f32 = lambda a: a.astype(jnp.float32)
    rows = jnp.concatenate(
        [stack[0][index, first:first + B],
         stack[1][index, first:first + B].swapaxes(1, 2)], axis=-1)
    pos = start[:, None] + jnp.arange(T)[None, :]
    return attend_whole(c, f32(q_nope), f32(q_rope), f32(rows), pos,
                        {k: f32(w) for k, w in layer.items()})


def attend(c, q_nope, q_rope, stack, index, first, rows, start, layer):
    """Through the model's own door, which has to take the kernel."""
    assert kernel.untileable(*by_head(q_nope, q_rope), *stack, layer["wuk"],
                             layer["wuv"], rows) is None
    return lm.attend_expanded(c, q_nope, q_rope, stack, index, first, rows,
                              start, layer)


# (rows of the chunk, where each sequence starts, read window, layer, slot)
CASES = {
    "at_row_0": (128, [0], 512, 0, 0),
    "mid_cache": (128, [200], 512, 0, 0),
    "ends_at_the_windows_last_row": (128, [384], 512, 0, 0),
    "two_sequences_at_different_starts": (128, [128, 300], 512, 0, 0),
    "another_layer_and_slot": (128, [77, 256], 512, 1, 1),
    "chunk_of_two_blocks": (256, [130], 512, 1, 2),
    "chunk_of_a_block": (128, [256], 512, 0, 1),
    "a_window_shorter_than_the_cache": (128, [100], 256, 1, 0),
    "ends_at_a_shorter_windows_last_row": (256, [0], 256, 0, 2),
}


@pytest.mark.parametrize("kv_rank", [128, 512])
@pytest.mark.parametrize("case", CASES)
def test_the_kernel_equals_the_whole_matrix_in_float32(case, kv_rank):
    c = dataclasses.replace(TILEABLE, kv_rank=kv_rank)
    T, start, rows, index, first = CASES[case]
    stack, layer, q_nope, q_rope, start = inputs(c, 3, T, start)
    got = attend(c, q_nope, q_rope, stack, index, first, rows, start, layer)
    assert got.shape == (len(start), T, c.n_heads, c.v_dim)
    assert got.dtype == c.dtype
    want = whole(c, q_nope, q_rope, stack, index, first, start, layer)
    assert rel_rms(got, want) < 1e-5


@pytest.mark.parametrize("case", CASES)
def test_the_kernel_in_bfloat16_rounds_where_the_block_loop_does(case):
    """Against the whole matrix in float32 to bfloat16's rounding, and
    against the ``jax.numpy`` form, whose rounding points are the
    kernel's, more closely."""
    c = dataclasses.replace(TILEABLE, dtype=jnp.bfloat16)
    T, start, rows, index, first = CASES[case]
    stack, layer, q_nope, q_rope, start = inputs(c, 4, T, start)
    got = attend(c, q_nope, q_rope, stack, index, first, rows, start, layer)
    assert got.dtype == jnp.bfloat16
    want = whole(c, q_nope, q_rope, stack, index, first, start, layer)
    assert rel_rms(got.astype(jnp.float32), want) < 2e-2
    # a sequence at a time: the CPU has no batched bfloat16 matmul into
    # float32 for the loop's einsums over two
    loop = jnp.concatenate([lm.attend_expanded_blockwise(
        c, q_nope[b:b + 1], q_rope[b:b + 1],
        lm._stack_reader(stack, index, first + b, 1), rows,
        start[b] + jnp.arange(T)[None, :], layer)
        for b in range(len(start))])
    assert rel_rms(got.astype(jnp.float32), loop.astype(jnp.float32)) < 6e-3


@pytest.mark.parametrize("block, tile", [(128, 128), (256, 128), (128, 256),
                                         (512, 256)])
def test_a_chunk_longer_than_equal_to_and_shorter_than_a_block(
        block, tile, monkeypatch):
    """A chunk of 256 rows over blocks of half, all and twice its rows,
    and one block for the whole window; in one tile and in two."""
    monkeypatch.setattr(kernel, "_BLOCK", block)
    monkeypatch.setattr(kernel, "_TILE", tile)
    c = TILEABLE
    stack, layer, q_nope, q_rope, start = inputs(c, 5, 256, [150, 256])
    got = attend(c, q_nope, q_rope, stack, 1, 1, 512, start, layer)
    want = whole(c, q_nope, q_rope, stack, 1, 1, start, layer)
    assert rel_rms(got, want) < 1e-5


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_nothing_behind_a_sequences_last_row_reaches_the_result(dtype):
    """Other garbage behind each sequence's last row (in its last
    block's tail and in the blocks after it), other layers and other
    lanes: the same result to the last bit."""
    c = dataclasses.replace(TILEABLE, dtype=dtype)
    stack, layer, q_nope, q_rope, start = inputs(c, 6, 128, [60, 200])
    got = attend(c, q_nope, q_rope, stack, 1, 1, 512, start, layer)
    latents, keys = stack
    for b, last in enumerate([60 + 127, 200 + 127]):
        latents = latents.at[1, 1 + b, last + 1:].set(1e6)
        keys = keys.at[1, 1 + b, :, last + 1:].set(1e6)
    latents = latents.at[0].set(1e6).at[1, 0].set(1e6)
    keys = keys.at[0].set(1e6).at[1, 0].set(1e6)
    again = attend(c, q_nope, q_rope, (latents, keys), 1, 1, 512, start,
                   layer)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(again))


@pytest.mark.parametrize("change, why", [
    (dict(nope_dim=16), "nope"), (dict(v_dim=64), "v="),
    (dict(kv_rank=32), "kv_rank"), (dict(rope_dim=8), "rope"),
    (dict(), "chunk rows")])
def test_what_the_kernel_cannot_tile_goes_to_the_block_loop(change, why):
    """A width that is no whole number of lanes, or a chunk of 64 rows:
    ``untileable`` says which, the kernel itself refuses, and
    ``attend_expanded`` gives the ``jax.numpy`` form's result."""
    c = dataclasses.replace(TILEABLE, **change)
    T = 128 if change else 64
    stack, layer, q_nope, q_rope, start = inputs(c, 7, T, [40, 300])
    reason = kernel.untileable(*by_head(q_nope, q_rope), *stack,
                               layer["wuk"], layer["wuv"], 512)
    assert why in reason
    with pytest.raises(NotImplementedError):
        kernel.latent_prefill_attention(
            *by_head(q_nope, q_rope), *stack, layer["wuk"], layer["wuv"], layer=1,
            slot=1, start_pos=start, rows=512, scale=1.0)
    got = lm.attend_expanded(c, q_nope, q_rope, stack, 1, 1, 512, start,
                             layer)
    want = whole(c, q_nope, q_rope, stack, 1, 1, start, layer)
    assert rel_rms(got, want) < 1e-5


# ------------------------------------------------------ the decode form
# a lane's heads are the rows of the decode kernel's matmuls: 16 of them
DECODABLE = dataclasses.replace(TILEABLE, n_heads=16)
IDLE = decoder.idle_position(CACHE_ROWS)

# (each lane's position, IDLE for a lane that is nobody's; layer; first
# lane; read window; the blocks of 128 rows each lane is taken through)
DECODE_CASES = {
    "lanes_of_unequal_length": ([300, 40, 510], 0, 0, 512, [3, 1, 4]),
    "last_rows_at_a_blocks_last_and_first_row": (
        [127, 128, 255], 1, 0, 512, [1, 2, 2]),
    "an_idle_lane_among_live_ones": ([200, IDLE, 77], 1, 0, 512, [2, 0, 1]),
    "every_lane_idle": ([IDLE, IDLE], 0, 1, 512, [0, 0]),
    "a_first_lane_that_is_not_0": ([130, 383], 1, 1, 512, [2, 3]),
    "one_lane_the_last_of_the_cache": ([256], 0, 2, 512, [3]),
    "the_shorter_read_window": ([100, 255, IDLE], 1, 0, 256, [1, 2, 0]),
}


def decode_inputs(c, seed, pos):
    stack, layer, q_nope, q_rope, pos = inputs(c, seed, 1, pos)
    return stack, layer, q_nope, q_rope, pos[:, None]


def absorbed(c, q_nope, q_rope, stack, index, first, rows, pos, layer):
    """Through the model's own door, which has to take the kernel ->
    (the lanes' results, the blocks it took each lane through)."""
    assert kernel.decode_untileable(c.n_heads, c.rope_dim, *stack) is None
    seen = jnp.where(pos[:, 0] == IDLE, 0, pos[:, 0] + 1)
    block, blocks = lm.absorbed_blocks(c, stack, rows, seen)
    assert block == 128
    return lm.attend_absorbed(c, q_nope, q_rope, stack, index, first, rows,
                              pos, layer, blocks), blocks.tolist()


def absorbed_loop(c, q_nope, q_rope, stack, index, first, rows, pos, layer):
    """``latent_moe``'s ``jax.numpy`` loop between the same two
    foldings, a lane at a time (the CPU has no batched bfloat16 matmul
    into float32), each through the blocks of its own last row."""
    out = []
    for b in range(len(pos)):
        q = jnp.einsum("bhk,chk->bhc", q_nope[b:b + 1, 0], layer["wuk"])
        mixed = lm.attend_absorbed_blockwise(
            c, q, q_rope[b:b + 1, 0],
            lm._stack_reader(stack, index, first + b, 1), rows, pos[b:b + 1],
            jnp.minimum(pos[b, 0], rows - 1) // lm._blocks(
                rows, lm.DECODE_BLOCK) + 1)
        out.append(jnp.einsum("bhc,chk->bhk", mixed, layer["wuv"])[:, None])
    return jnp.concatenate(out)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", DECODE_CASES)
def test_the_decode_kernel_equals_the_block_loop(case, dtype):
    """Every live lane's result is the loop's (whose rounding points are
    the kernel's; its blocks are the whole window here, the kernel's 128
    rows); each lane is taken through the blocks of its own last row and
    an idle lane through none, whatever its rows hold (NaN here, as are
    the rows behind every lane's last block, the other layer and the
    lanes outside the call): its result is zeros and the live lanes'
    are the same to the last bit."""
    c = dataclasses.replace(DECODABLE, dtype=dtype)
    pos, index, first, rows, want_blocks = DECODE_CASES[case]
    stack, layer, q_nope, q_rope, pos = decode_inputs(c, 11, pos)
    args = (index, first, rows, pos, layer)
    got, blocks = absorbed(c, q_nope, q_rope, stack, *args)
    assert blocks == want_blocks
    assert got.shape == (len(pos), 1, c.n_heads, c.v_dim)
    assert got.dtype == dtype
    live = np.flatnonzero(np.asarray(pos[:, 0]) != IDLE)
    got32 = np.asarray(got.astype(jnp.float32))
    assert not got32[np.asarray(pos[:, 0]) == IDLE].any()
    if len(live):
        want = absorbed_loop(c, q_nope, q_rope, stack, *args)
        assert rel_rms(got32[live], want.astype(jnp.float32)[live]) < (
            1e-5 if dtype == jnp.float32 else 6e-3)
    # what no lane was taken through may hold anything
    fetched = np.zeros((LAYERS, LANES, CACHE_ROWS), bool)
    for b, n in enumerate(want_blocks):
        fetched[index, first + b, :n * 128] = True
    latents = jnp.where(fetched[..., None], stack[0], jnp.nan)
    keys = jnp.where(fetched[:, :, None, :], stack[1], jnp.nan)
    again, _ = absorbed(c, q_nope, q_rope, (latents, keys), *args)
    np.testing.assert_array_equal(got32, np.asarray(again, np.float32))


@pytest.mark.parametrize("block, tile", [(128, 128), (256, 128), (512, 128),
                                         (256, 256), (512, 1024)])
def test_a_decode_block_of_one_two_and_four_tiles(block, tile, monkeypatch):
    """A block is scored and weighed in tiles, each with the running
    maximum's update of its own: blocks of one tile, of two and the
    whole cache as one block of four (and a tile larger than the block,
    which is then the block), lanes ending in a block's first tile, in
    its last and at the cache's last row but one."""
    monkeypatch.setattr(kernel, "_DECODE_BLOCK", block)
    monkeypatch.setattr(kernel, "_DECODE_TILE", tile)
    c = DECODABLE
    stack, layer, q_nope, q_rope, pos = decode_inputs(c, 16, [260, 127, 510])
    seen = pos[:, 0] + 1
    rows, blocks = lm.absorbed_blocks(c, stack, 512, seen)
    assert rows == block
    assert blocks.tolist() == [-(-int(n) // block) for n in seen]
    got = lm.attend_absorbed(c, q_nope, q_rope, stack, 1, 0, 512, pos, layer,
                             blocks)
    want = absorbed_loop(c, q_nope, q_rope, stack, 1, 0, 512, pos, layer)
    assert rel_rms(got, want) < 1e-5


def test_both_read_windows_give_the_decode_kernels_result_to_the_last_bit():
    """The kernel takes no read window: lanes that fit the cache's
    first half give the same bits at either, and the engine's two
    decode variants hold one trace of it."""
    c = DECODABLE
    stack, layer, q_nope, q_rope, pos = decode_inputs(c, 12, [10, 255, IDLE])
    half, _ = absorbed(c, q_nope, q_rope, stack, 1, 0, 256, pos, layer)
    whole_window, _ = absorbed(c, q_nope, q_rope, stack, 1, 0, 512, pos, layer)
    np.testing.assert_array_equal(np.asarray(half), np.asarray(whole_window))


def test_two_shards_in_one_decode_call_are_each_their_own_call():
    """``decoder.Call.by_shard`` as ``forward_with_cache`` uses it: four
    lanes over a pair of caches, the per-lane blocks cut like the
    queries; each shard's lanes get what a call of that shard alone
    gives, to the last bit."""
    c = DECODABLE
    shards = [decode_inputs(c, seed, at) for seed, at in (
        (13, [300, IDLE]), (14, [5, 140]))]
    layer = shards[0][1]
    q_nope, q_rope, pos = (jnp.concatenate([s[i] for s in shards])
                           for i in (2, 3, 4))
    # two lanes a cache
    stacks = tuple(tuple(leaf[:, :2] for leaf in s[0]) for s in shards)
    call = decoder.Call(jnp.zeros((4, 1), jnp.int32), pos[:, 0], CACHE_ROWS,
                        shards=2)
    seen = jnp.where(call.live(), call.pos + 1, 0)[:, 0]
    _, blocks = lm.absorbed_blocks(c, stacks[0], CACHE_ROWS, seen)
    assert blocks.tolist() == [3, 0, 1, 2]

    def attend(part, stack, q_nope, q_rope, blocks):
        return lm.attend_absorbed(c, q_nope, q_rope, stack, 1, 0, CACHE_ROWS,
                                  part.pos, layer, blocks), stack

    got, _ = call.by_shard(attend, stacks, q_nope, q_rope, blocks)
    for s, stack in enumerate(stacks):
        at = slice(2 * s, 2 * s + 2)
        alone, _ = absorbed(c, q_nope[at], q_rope[at], stack, 1, 0,
                            CACHE_ROWS, pos[at], layer)
        np.testing.assert_array_equal(np.asarray(got[at]), np.asarray(alone))


@pytest.mark.parametrize("change, why", [
    (dict(kv_rank=32), "kv_rank"), (dict(rope_dim=8), "rope"),
    (dict(n_heads=2), "heads"), (dict(), "cache rows")])
def test_what_the_decode_kernel_cannot_tile_goes_to_the_block_loop(
        change, why):
    """A latent row that is no whole number of lanes, a rotary part or
    a count of heads that is no whole number of sublanes, or a cache of
    192 rows: ``decode_untileable`` says which, the kernel itself
    refuses, and ``attend_absorbed`` takes every lane through the
    longest live lane's blocks in the ``jax.numpy`` form."""
    c = dataclasses.replace(DECODABLE, **change)
    stack, layer, q_nope, q_rope, pos = decode_inputs(c, 15, [40, 150, 191])
    if not change:
        stack = (stack[0][:, :, :192], stack[1][..., :192])
    rows = stack[0].shape[2]
    assert why in kernel.decode_untileable(c.n_heads, c.rope_dim, *stack)
    seen = jnp.asarray([41, 151, 0])
    block, blocks = lm.absorbed_blocks(c, stack, rows, seen)
    assert block == rows and blocks.tolist() == [1, 1, 1]
    with pytest.raises(NotImplementedError):
        kernel.latent_decode_attention(
            jnp.zeros((3, c.n_heads, c.kv_rank)), q_rope[:, 0], *stack,
            layer=1, slot=0, pos=pos[:, 0], blocks=blocks, scale=1.0)
    got = lm.attend_absorbed(c, q_nope, q_rope, stack, 1, 0, rows, pos, layer,
                             blocks)
    rows_as_one = jnp.concatenate(
        [stack[0][1], stack[1][1].swapaxes(1, 2)], axis=-1)
    want = attend_whole(c, q_nope, q_rope, rows_as_one, pos, layer)
    assert rel_rms(got[:2], want[:2]) < 1e-5
