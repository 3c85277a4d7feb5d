"""A held share's expert passes in slabs (``ops/moe.py`` ``_held_slabs``):
where a share of the experts is held and a call is past the every-expert
path, only the assignments to held experts are gathered, multiplied,
zeroed behind and summed back, ``_slab`` of them a pass. Toy widths on
the CPU in float32, against the float32 reference of
``benchmarks/families/pangu_reference.py`` (``routed_part``, which
imports nothing of ``ray_tpu``), at shapes where the slab is smaller
than the call: 256 rows, top-4, 4 of 16 held: 1024 assignments, slabs of
512."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.families import pangu_reference  # noqa: E402
from ray_tpu.models import latent_moe as lm  # noqa: E402
from ray_tpu.models import parallel_moe as pm  # noqa: E402
from ray_tpu.ops import moe  # noqa: E402

T, K, HELD = 256, 4, (4, 5, 6, 7)
N, SLAB = T * K, 512
SHARED = ("shared_gate", "shared_up", "shared_down")


def share(D, F):
    return moe.MoEConfig(d_model=D, d_ff=F, n_experts=16, k=K,
                         scoring="sigmoid", routed_scale=2.5, held=HELD)


def layer_params(seed, D=32, F=16):
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    n = lambda k, *s: jax.random.normal(k, s, jnp.float32) / np.sqrt(s[-2])
    return {"router": n(keys[0], D, 16),
            "w_gate": n(keys[1], 4, D, F), "w_up": n(keys[2], 4, D, F),
            "w_down": n(keys[3], 4, F, D),
            "shared_gate": n(keys[4], D, 24), "shared_up": n(keys[5], D, 24),
            "shared_down": n(keys[6], 24, D)}


def rel_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / np.sqrt(np.mean(want ** 2)))


def forced(params, x, all_held: int, one_held: int = 0):
    """The router and the rows bent so that the first ``all_held`` rows
    send all four assignments to the held experts, the ``one_held`` rows
    behind them one (to the first held expert) and the rest none: the
    rows' first two features say which, and the router reads them
    alone beside its seeded noise."""
    held, others = np.asarray(HELD), np.setdiff1d(np.arange(16), HELD)
    router = np.array(params["router"])
    router[0, held], router[0, others] = 5.0, -5.0
    router[1] = 0.0
    router[1, held[0]], router[1, held[1:]] = 5.0, -5.0
    x = np.array(x)
    x[:, 0], x[:, 1] = -10.0, 0.0
    x[:all_held, 0] = 10.0
    x[all_held:all_held + one_held, :2] = 0.0, 10.0
    return {**params, "router": jnp.asarray(router)}, jnp.asarray(x)


# name -> (rows with all four assignments held, rows with one, passes)
ROUTINGS = {
    "every_assignment_held": (T, 0, N // SLAB),
    "none_held": (0, 0, 0),
    "exactly_a_slab": (SLAB // K, 0, 1),
    "a_slab_and_one": (SLAB // K, 1, 2),    # the last expert straddles
    "an_expert_across_the_edge": (200, 0, 2),
    "one_assignment": (0, 1, 1),
}


def reference(params, x, config):
    layer = {k: v for k, v in params.items() if k not in SHARED}
    with jax.default_matmul_precision("highest"):
        routed = pangu_reference.routed_part(
            x, layer, held=config.held, top_k=config.k, norm_topk=True,
            scale=config.routed_scale)
        shared = pangu_reference._swiglu(x, *(params[k] for k in SHARED))
    return np.asarray(routed), np.asarray(shared)


def held_assignments(params, x, config) -> int:
    _, chosen = jax.lax.top_k(jax.nn.sigmoid(x @ params["router"]), config.k)
    return int(np.isin(np.asarray(chosen), config.held).sum())


def test_the_slab_is_twice_the_even_share_in_whole_row_tiles():
    assert moe._slab(share(32, 16), N) == SLAB
    wide = lambda held, n: moe.MoEConfig(
        d_model=8, d_ff=8, n_experts=n, k=8, held=tuple(range(held)))
    # the two served configurations' chunk buckets
    assert [moe._slab(wide(8, 256), rows * 8)
            for rows in (1024, 512, 256)] == [512, 256, 128]
    assert [moe._slab(wide(16, 128), rows * 8)
            for rows in (1024, 512, 256)] == [2048, 1024, 512]
    # every expert held, or a call too small to compact: all of it
    assert moe._slab(wide(16, 16), 1024) == 1024
    assert moe._slab(dataclasses.replace(wide(16, 16), held=None), 96) == 96
    assert moe._slab(share(32, 16), 96) == 96


def test_a_seeded_routing_takes_one_pass_and_equals_the_reference():
    config, params = share(32, 16), layer_params(0)
    x = jax.random.normal(jax.random.PRNGKey(1), (T, 32))
    n = held_assignments(params, x, config)
    assert 0.15 * N < n < SLAB           # an even router: a quarter
    out, counts = jax.jit(lambda p, x: moe.moe_ffn_dropless(p, x, config))(
        params, x)
    routed, shared = reference(params, x, config)
    assert rel_rms(out, routed + shared) < 1e-5
    assert [int(c) for c in counts] == [n, 4, 4, 1]


@pytest.mark.parametrize("name", list(ROUTINGS))
def test_every_routing_is_computed_whole(name):
    """The passes follow the routing: N / slab of them where every
    assignment lands here, none where none does (the shared expert
    alone is left), two where the held share is one over a slab; an
    expert whose interval crosses a slab's edge is read in both."""
    all_held, one_held, passes = ROUTINGS[name]
    config = share(32, 16)
    params, x = forced(layer_params(2), jax.random.normal(
        jax.random.PRNGKey(3), (T, 32)), all_held, one_held)
    n = K * all_held + one_held
    assert held_assignments(params, x, config) == n
    out, counts = jax.jit(lambda p, x: moe.moe_ffn_dropless(p, x, config))(
        params, x)
    routed, shared = reference(params, x, config)
    assert rel_rms(out, routed + shared) < 1e-5
    touched = 4 if all_held else min(one_held, 1)
    assert [int(c) for c in counts] == [n, touched, 4, passes]
    if name == "none_held":
        alone, _ = moe.moe_ffn_dropless(
            {k: v for k, v in params.items() if k not in SHARED}, x, config)
        assert np.abs(np.asarray(alone)).max() == 0
        assert rel_rms(out, shared) < 1e-6


@pytest.mark.parametrize("name", ["seeded", "every_assignment_held",
                                  "a_slab_and_one"])
def test_the_slabs_go_through_the_kernel_where_it_tiles(name):
    """128 wide: the slab of 512 is four of the kernel's row tiles, and
    the kernel (interpreted here) is what a pass multiplies with."""
    config, params = share(128, 128), layer_params(4, 128, 128)
    x = jax.random.normal(jax.random.PRNGKey(5), (T, 128))
    passes = 1
    if name != "seeded":
        all_held, one_held, passes = ROUTINGS[name]
        params, x = forced(params, x, all_held, one_held)
    f = lambda p, x: moe.moe_ffn_dropless(p, x, config)
    text = str(jax.make_jaxpr(f)(params, x))
    assert "grouped_swiglu" in text and "ragged_dot" not in text
    out, counts = jax.jit(f)(params, x)
    routed, shared = reference(params, x, config)
    assert np.isfinite(np.asarray(out)).all()
    assert rel_rms(out, routed + shared) < 1e-5
    assert int(counts[3]) == passes


def test_rows_that_are_nobodys_are_computed_and_not_counted():
    config, params = share(32, 16), layer_params(6)
    x = jax.random.normal(jax.random.PRNGKey(7), (T, 32))
    live = np.arange(T) < 100
    out, counts = moe.moe_ffn_dropless(params, x, config,
                                       live=jnp.asarray(live))
    whole, _ = moe.moe_ffn_dropless(params, x, config)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(whole))
    _, chosen = jax.lax.top_k(jax.nn.sigmoid(x @ params["router"]), K)
    mine = np.asarray(chosen)[live]
    mine = mine[np.isin(mine, HELD)]
    assert [int(c) for c in counts] == [len(mine), len(set(mine)), 4, 1]


@pytest.mark.parametrize("name", ["seeded", "a_slab_and_one"])
def test_what_lies_behind_a_slabs_last_group_never_reaches_the_sum(
        name, monkeypatch):
    """The grouped matmul does not write the rows behind its last group
    (``ops/pallas_grouped_matmul.py``; on the chip they hold whatever the
    memory held): with NaN left there, the layer's output is the same."""
    config, params = share(32, 16), layer_params(8)
    x = jax.random.normal(jax.random.PRNGKey(9), (T, 32))
    if name != "seeded":
        params, x = forced(params, x, *ROUTINGS[name][:2])
    want, _ = moe.moe_ffn_dropless(params, x, config)
    sound, calls = moe.expert_ffn, []

    def leaves_nan_behind(xs, w_gate, w_up, w_down, group_sizes, layer=None):
        ys = sound(xs, w_gate, w_up, w_down, group_sizes, layer)
        calls.append(xs.shape)
        behind = jnp.arange(xs.shape[0]) >= group_sizes.sum()
        return jnp.where(behind[:, None], jnp.nan, ys)

    monkeypatch.setattr(moe, "expert_ffn", leaves_nan_behind)
    got, _ = moe.moe_ffn_dropless(params, x, config)
    assert calls == [(SLAB, 32)]         # traced once: the loop's body
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_under_a_mesh_each_shard_of_the_rows_loops_over_its_own_slabs():
    from jax.sharding import Mesh

    config = share(32, 16)
    params, x = forced(layer_params(10), jax.random.normal(
        jax.random.PRNGKey(11), (4 * T, 32)), T + 8)
    f = lambda p, x: moe.moe_ffn_dropless(p, x, config)
    routed, shared = reference(params, x, config)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "fsdp"))
    with jax.sharding.set_mesh(mesh):
        got, counts = jax.jit(f)(params, x)
    assert rel_rms(got, routed + shared) < 1e-5
    # the first shard's rows are all held, the second's first 8, the
    # others' none: 2 + 1 + 0 + 0 passes
    assert [int(c) for c in counts] == [K * (T + 8), 8, 16, 3]


# ------------------------------------------------ the families' programs
FAMILIES = {"latent_moe": (lm, lm.LATENT_MOE_TINY),
            "parallel_moe": (pm, pm.PARALLEL_MOE_TINY)}


def chunk_text(module, config, rows):
    params = jax.eval_shape(
        lambda: module.init_params(jax.random.PRNGKey(0), config))
    cache = jax.eval_shape(lambda: module.init_cache(config, 1, 256, rows))
    return jax.jit(lambda p, t, k, s: module.forward_with_cache(
        p, t, k, s, config, slot=jnp.int32(0),
        logits_at=jnp.zeros(1, jnp.int32))).lower(
            params, jax.ShapeDtypeStruct((1, rows), jnp.int32), cache,
            jax.ShapeDtypeStruct((1,), jnp.int32)).as_text()


@pytest.mark.parametrize("family", list(FAMILIES))
def test_no_chunk_program_holds_a_product_of_every_assignment(family):
    """The lowered text of a chunk program of 192 rows (768 assignments
    at the tiny presets' top-4, 4 of 16 held: slabs of 384): no float32
    tensor of assignments x width is left, which the parent's product,
    its zeroing and its gather back each were; with every expert held
    the three are there as they were."""
    module, tiny = FAMILIES[family]
    rows, D = 192, tiny.dim
    assert moe._slab(tiny.moe, rows * 4) == 384
    text = chunk_text(module, tiny, rows)
    assert f"tensor<{rows * 4}x{D}x" not in text
    assert f"tensor<384x{D}xf32>" in text
    every = dataclasses.replace(tiny, held_experts=tuple(range(16)))
    assert f"tensor<{rows * 4}x{D}xf32>" in chunk_text(module, every, rows)


@pytest.mark.parametrize("family", list(FAMILIES))
def test_the_families_count_a_pass_a_layer_a_chunk_call(family):
    """``moe_held_slabs`` beside the family's other words: one a routed
    layer for a chunk call whose held share fits the slab, nothing for a
    decode call (every row through every expert)."""
    module, tiny = FAMILIES[family]
    c = dataclasses.replace(tiny, dtype=jnp.float32, param_dtype=jnp.float32)
    layers = getattr(c, "n_routed_layers", c.n_layers)
    params = module.init_params(jax.random.PRNGKey(0), c)
    cache = module.init_cache(c, 2, 256, 128)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 128), 0,
                                c.vocab_size)
    _, cache = module.forward_with_cache(
        params, tokens, cache, jnp.zeros(1, jnp.int32), c,
        slot=jnp.int32(1), logits_at=jnp.asarray([99]))
    got = module.read_counters(cache)
    assert got["moe_held_slabs"] == layers
    assert got["moe_assignments_all"] == layers * 100 * 4
    assert 0 < got["moe_assignments"] < got["moe_assignments_all"]
    _, cache = module.forward_with_cache(
        params, jnp.asarray([[0], [5]]), cache, jnp.asarray([255, 100]), c)
    again = module.read_counters(cache)
    assert again["moe_held_slabs"] == layers
    assert again["moe_assignments_all"] == layers * 101 * 4
