"""The ``mellum`` family: decoders that ``models/window_moe.py`` runs
(Mellum2-12B-A2.5B): pre-norm GQA attention at a head size of its own,
sliding-window layers beside full layers with a YaRN table, and in every
layer a dropless top-k mixture of SwiGLU experts. ``hp`` is the
configuration file's dict (the published config.json keys).

The family is served only, so it gives what "A served family" of
README.md lists and nothing of a trained one: no ``loss_fn``, no
``logits`` and no train FLOPs, because the program has no backward pass
of the dropless expert layer and no cell trains it; a train cell of this
family fails on the missing name, it is never skipped.
"""

from __future__ import annotations

from benchmarks.families import mellum_reference

LAYER_KINDS = {"sliding_attention": "sliding", "full_attention": "full"}


def model_config(hp: dict, options: dict = None):
    """The repo's configuration object for these published sizes, bf16
    parameters (float32 router); serving passes no ``options``."""
    import jax.numpy as jnp

    from ray_tpu.models.window_moe import WindowMoEConfig, YarnRope

    if options is not None:
        raise ValueError("the mellum family is served only: no train options")
    layers = hp["num_hidden_layers"]
    if set(hp["mlp_layer_types"][:layers]) != {"sparse"}:
        raise ValueError("models/window_moe.py routes every layer's MLP")
    rope = hp["rope_parameters"]
    sliding, full = rope["sliding_attention"], rope["full_attention"]
    if sliding["rope_type"] != "default" or full["rope_type"] != "yarn":
        raise ValueError("expected a default table for the sliding layers "
                         "and a YaRN table for the full ones")
    # bfloat16 as served; the toy rehearsal preset computes in float32
    # (the configuration's file says why)
    dtype = getattr(jnp, hp.get("compute_dtype", "bfloat16"))
    return WindowMoEConfig(
        vocab_size=hp["vocab_size"], dim=hp["hidden_size"], n_layers=layers,
        n_heads=hp["num_attention_heads"],
        n_kv_heads=hp["num_key_value_heads"], head_size=hp["head_dim"],
        ffn_dim=hp["intermediate_size"],    # the dense layers': none here
        max_seq_len=hp["max_position_embeddings"],
        rope_theta=float(sliding["rope_theta"]),
        norm_eps=float(hp["rms_norm_eps"]), dtype=dtype, param_dtype=dtype,
        remat=False,
        layer_types=tuple(LAYER_KINDS[t] for t in hp["layer_types"][:layers]),
        sliding_window=hp["sliding_window"],
        full_rope=YarnRope(
            theta=float(full["rope_theta"]), factor=float(full["factor"]),
            original_max_position=full["original_max_position_embeddings"],
            beta_fast=float(full["beta_fast"]),
            beta_slow=float(full["beta_slow"]),
            attention_factor=full.get("attention_factor")),
        n_experts=hp["num_experts"],
        experts_per_token=hp["num_experts_per_tok"],
        expert_dim=hp["moe_intermediate_size"],
        norm_topk_prob=hp["norm_topk_prob"])


def init_params(key, cfg):
    from ray_tpu.models import window_moe

    return window_moe.init_params(key, cfg)


# -- the plain float32 reference (imports nothing of ray_tpu) ----------
def reference_logits(params, tokens, hp: dict, last: int = 0):
    return mellum_reference.logits(params, tokens, hp, last=last)


# -- what names an op in a device trace --------------------------------
# the jax.named_scope names of models/window_moe.py, ops/moe.py and the
# engine's programs
SCOPES = ("embed", "layers", "attn", "moe", "moe_router", "moe_dispatch",
          "moe_experts", "moe_combine", "head", "kv_write", "attn_cached",
          "attn_window", "kv_slice", "sample")
NAMED_OPS = "^(ragged-dot|all-gather|all-reduce|reduce-scatter|all-to-all)"
KV_SCOPES = ("kv_slice", "kv_write")
COMPUTE_SCOPES = ("embed", "attn", "moe", "head", "sample")
