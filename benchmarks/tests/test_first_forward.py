"""train_loop.first_forward, the per-token part of a train cell's
``correct``: its arithmetic made a second time by a plain loop on a
family whose logits are off by a known error; the control (the MLP
matmuls' operands rounded to fp8, nothing else) at toy size, which must
fail where the program as it is passes; the toy family through the same
door; and the step's scalars that pass through as ``step.<name>``."""

import math
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import spec, traffic, train_loop
from ray_tpu import parallel

TRAIN = [w["name"] for w in spec.benchmark_json()["workloads"]
         if spec.load_cell(w["name"], True)["kind"] == "train"]


def built(cell_name, seed):
    """The cell's rehearsal preset as train_loop.train_loop builds it:
    (family, cfg, mesh, hp, params, probe, the probe batch, the limit)."""
    cell = spec.load_cell(cell_name, rehearse=True)
    hp, tf, opts = cell["hp"], cell["traffic"], cell["train"]
    family = spec.family_of(hp)
    cfg = family.model_config(hp, opts)
    mesh = parallel.make_mesh(devices=jax.devices()[:1])
    params = jax.jit(lambda key: family.init_params(key, cfg))(
        spec.prng_key(seed))
    probe = traffic.probe_sequence(seed, tf["seq"] + 1, hp["vocab_size"])
    tokens = jax.device_put(np.ascontiguousarray(np.broadcast_to(
        probe, (tf["seqs_per_chip"], tf["seq"] + 1))),
        parallel.batch_sharding(mesh))
    return (family, cfg, mesh, hp, params, probe, tokens,
            opts["reference_nll_rms_tol"])


def off_by(family, relative, seed):
    """A family whose logits are the reference's plus seeded noise of
    ``relative`` times their root mean square: an error of a known size
    that no fusion or rounding of the day can move."""
    def logits(params, tokens, config):
        ref = family.reference_logits(params, tokens[0], config)
        noise = jax.random.normal(jax.random.PRNGKey(seed), ref.shape)
        noisy = ref + relative * jnp.sqrt(jnp.mean(ref ** 2)) * noise
        return jnp.broadcast_to(noisy, (tokens.shape[0],) + ref.shape)
    return types.SimpleNamespace(
        __name__="off_by", logits=logits,
        reference_logits=family.reference_logits)


@pytest.mark.parametrize("relative,passes", [(0.012, True), (0.09, False)])
def test_the_numbers_compared_are_the_plain_loops(relative, passes):
    """Logits off by what bf16 operands cost through a few layers (1.2 %
    of their RMS) pass the cell's limit, off by what fp8 operands cost
    (9 %) fail it; each number first_forward gives is made again here."""
    family, cfg, mesh, hp, params, probe, tokens, tol = built(TRAIN[0], 5)
    fake = off_by(family, relative, seed=11)
    got = train_loop.first_forward(fake, hp, mesh, hp, params, probe, tokens)
    assert (got["finite"] and got["nll_rms"] <= tol) is passes

    mine = np.asarray(fake.logits(params, tokens[:, :-1], hp)[0], np.float64)
    ref = np.asarray(family.reference_logits(
        params, jnp.asarray(probe[:-1]), hp), np.float64)

    def nll(row, target):
        top = max(row)
        return top + math.log(sum(math.exp(x - top) for x in row)) \
            - row[target]

    diffs, mean_mine, mean_ref = [], 0.0, 0.0
    for t in range(len(probe) - 1):
        a, b = nll(mine[t], probe[t + 1]), nll(ref[t], probe[t + 1])
        diffs.append(a - b)
        mean_mine += a / (len(probe) - 1)
        mean_ref += b / (len(probe) - 1)
    assert got["nll_rms"] == pytest.approx(
        math.sqrt(sum(d * d for d in diffs) / len(diffs)), rel=1e-3)
    assert got["nll_mean"] == pytest.approx(mean_mine, rel=1e-5)
    assert got["reference_nll_mean"] == pytest.approx(mean_ref, rel=1e-5)
    last = min(train_loop.LAST_LOGITS, len(probe) - 1)
    num = sum((x - y) ** 2 for r, s in zip(mine[-last:], ref[-last:])
              for x, y in zip(r, s))
    den = sum(y * y for s in ref[-last:] for y in s)
    assert got["last_logits_rel_rms"] == pytest.approx(
        math.sqrt(num / den), rel=1e-3)
    # and the mean of a few hundred NLLs, which is what the first-loss
    # check compares, moves far less than their RMS: why there are two
    assert abs(got["nll_mean"] - got["reference_nll_mean"]) \
        < 0.3 * got["nll_rms"]


def fp8(a):
    """Rounded to 4 exponent and 3 mantissa bits under a per-tensor
    scale (reduce_precision: XLA removes a pair of converts)."""
    f = a.astype(jnp.float32)
    scale = jnp.max(jnp.abs(f)) / 240.0
    return (jax.lax.reduce_precision(f / scale, exponent_bits=4,
                                     mantissa_bits=3) * scale).astype(a.dtype)


def mlp_in_fp8(config, x, layer):
    """``llama.mlp_sublayer`` with every operand of its three matmuls in
    fp8 and nothing else changed: the control, patched over the model in
    the test's (or a scratch script's) own process, never in the
    program."""
    from ray_tpu.models import llama

    c = config
    h = fp8(llama.rms_norm(x, layer["mlp_norm"], c.norm_eps))
    gate = jnp.einsum("bsd,df->bsf", h, fp8(layer["w_gate"].astype(c.dtype)))
    up = jnp.einsum("bsd,df->bsf", h, fp8(layer["w_up"].astype(c.dtype)))
    return x + jnp.einsum("bsf,fd->bsd", fp8(jax.nn.silu(gate) * up),
                          fp8(layer["w_down"].astype(c.dtype)))


@pytest.mark.parametrize("seed", [3, 2**31 + 5, 2**31 + 77])
@pytest.mark.parametrize("cell", TRAIN)
def test_the_control_fails_where_the_program_passes(cell, seed, monkeypatch):
    """The control of PERF.md section 4 at a size a test can hold: the
    program as it is reads under the limit with room, and with the three
    MLP matmuls' operands in fp8 and nothing else changed, over it. On
    the chip, at the cells' own sizes, it was a scratch script that
    patched the model in the same way."""
    from ray_tpu.models import llama

    family, cfg, mesh, hp, params, probe, tokens, tol = built(cell, seed)
    sound = train_loop.first_forward(
        family, cfg, mesh, hp, params, probe, tokens)
    assert sound["finite"] and sound["nll_rms"] <= 0.6 * tol, sound

    monkeypatch.setattr(llama, "mlp_sublayer", mlp_in_fp8)
    control = train_loop.first_forward(
        family, cfg, mesh, hp, params, probe, tokens)
    assert control["nll_rms"] >= 1.5 * tol, control
    # the scalar the first-loss check compares cannot tell them apart
    opts = spec.load_cell(cell, True)["train"]
    assert abs(control["nll_mean"] - control["reference_nll_mean"]) \
        < opts["reference_loss_tol"]


def test_the_toy_family_goes_through_the_same_door():
    """A mixture of experts whose loss carries a router term: the
    per-token check covers its cross entropy, and it keeps a
    reference_loss of its own for the first-loss check."""
    family, cfg, mesh, hp, params, probe, tokens, tol = built(
        "toy-moe.train", 2**31 + 9)
    got = train_loop.first_forward(family, cfg, mesh, hp, params, probe, tokens)
    assert got["finite"] and 0 < got["nll_rms"] <= tol
    whole = float(family.reference_loss(params, jnp.asarray(probe), hp))
    assert whole > got["reference_nll_mean"]        # the router's term
    from benchmarks.families import llama as dense
    assert not hasattr(dense, "reference_loss")     # one pass, not two


def test_a_family_without_logits_is_a_clear_error():
    family, cfg, mesh, hp, params, probe, tokens, _ = built(TRAIN[0], 1)
    old = types.SimpleNamespace(
        __name__="benchmarks.families.old",
        reference_logits=family.reference_logits)
    with pytest.raises(ValueError, match="old gives no logits"):
        train_loop.first_forward(old, cfg, mesh, hp, params, probe, tokens)


def test_step_scalars_are_every_scalar_but_the_loss():
    metrics = {"loss": jnp.float32(2.0), "grad_norm": jnp.float32(0.5),
               "step": jnp.int32(7), "expert_load": jnp.ones((4,)),
               "note": "not a number"}
    kept = train_loop.step_scalars(metrics)
    assert set(kept) == {"grad_norm", "step"}
    assert kept["grad_norm"] is metrics["grad_norm"]    # still on the device
