"""The ``jamba`` family: decoders that ``models/hybrid_ssm.py`` runs
(AI21-Jamba2-3B): state-space (Mamba-1) mixers with an attention layer
every ``attn_layer_period``, attention without a position term over one
key/value head, a dense SwiGLU behind every mixer, a tied head. ``hp`` is
the configuration file's dict: the published config.json keys.

Served only, as the ``mellum`` and ``pangu`` families are: it gives what
"A served family" of README.md lists and nothing of a trained one; a
train cell of this family fails on the missing name, it is never
skipped.
"""

from __future__ import annotations

from benchmarks.families import jamba_reference


def model_config(hp: dict, options: dict = None):
    """The repo's configuration object for these published sizes, bf16
    parameters (the state-space parameters float32); serving passes no
    ``options``."""
    import jax.numpy as jnp

    from ray_tpu.models.hybrid_ssm import HybridSSMConfig

    if options is not None:
        raise ValueError("the jamba family is served only: no train options")
    if hp["num_experts"] != 1 or not hp["tie_word_embeddings"]:
        raise ValueError("models/hybrid_ssm.py has a dense SwiGLU in every "
                         "layer (num_experts 1) and a tied head")
    if hp["mamba_proj_bias"] or not hp["mamba_conv_bias"]:
        raise ValueError("models/hybrid_ssm.py has a bias on the "
                         "convolution and none on the projections")
    # bfloat16 as served; the toy rehearsal preset computes in float32
    dtype = getattr(jnp, hp.get("compute_dtype", "bfloat16"))
    return HybridSSMConfig(
        vocab_size=hp["vocab_size"], dim=hp["hidden_size"],
        n_layers=hp["num_hidden_layers"], n_heads=hp["num_attention_heads"],
        n_kv_heads=hp["num_key_value_heads"], head_size=hp["head_dim"],
        ffn_dim=hp["intermediate_size"],
        max_seq_len=hp["max_position_embeddings"],
        norm_eps=float(hp["rms_norm_eps"]), dtype=dtype, param_dtype=dtype,
        remat=False,
        attn_period=hp["attn_layer_period"], attn_offset=hp["attn_layer_offset"],
        d_state=hp["mamba_d_state"], d_conv=hp["mamba_d_conv"],
        expand=hp["mamba_expand"], dt_rank=hp["mamba_dt_rank"])


def init_params(key, cfg):
    from ray_tpu.models import hybrid_ssm

    return hybrid_ssm.init_params(key, cfg)


# -- the plain float32 reference (imports nothing of ray_tpu) ----------
def reference_logits(params, tokens, hp: dict, last: int = 0):
    return jamba_reference.logits(params, tokens, hp, last=last)


# -- what names an op in a device trace --------------------------------
# the jax.named_scope names of models/hybrid_ssm.py, models/llama.py's
# sublayers and the engine's programs
SCOPES = ("embed", "layers", "attn", "ssm", "ssm_in", "ssm_conv", "ssm_x",
          "ssm_dt", "ssm_scan", "ssm_step", "ssm_out", "mlp", "head",
          "kv_write", "attn_cached", "kv_slice", "state_slice",
          "state_write", "sample")
NAMED_OPS = "^(selective_scan_|all-gather|all-reduce|reduce-scatter|all-to-all)"
# the scopes that move the cache (keys and values by position; a lane's
# state and tail out of the stacks and back) and those that compute
KV_SCOPES = ("kv_slice", "kv_write", "state_slice", "state_write")
COMPUTE_SCOPES = ("embed", "attn", "ssm", "mlp", "head", "sample")
