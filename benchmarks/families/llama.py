"""The ``llama`` family: dense pre-norm decoders that ``models/llama.py``
runs (InternLM2, Mistral): RMSNorm, grouped-query causal attention with
rotary embeddings, SwiGLU, untied head. The only harness file that
imports ``ray_tpu.models.llama`` or ``benchmarks.reference``; every other
reaches them through ``spec.family_of(hp)``. ``hp`` is a configuration
file's dict (Hugging Face key names); ``options`` a train cell's
``train`` block.
"""

from __future__ import annotations

from benchmarks import flops, reference

# -- the program -------------------------------------------------------
# what a train cell may set on the model, from its ``train`` block
TRAIN_OPTIONS = ("remat", "attention_impl", "ce_impl")


def model_config(hp: dict, options: dict = None):
    """The repo's configuration object for these published sizes, bf16
    parameters; ``options`` is a train cell's block (serving passes
    none)."""
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig

    if hp["head_dim"] * hp["num_attention_heads"] != hp["hidden_size"]:
        raise ValueError("models/llama.py derives head_dim as dim / n_heads")
    return LlamaConfig(
        vocab_size=hp["vocab_size"], dim=hp["hidden_size"],
        n_layers=hp["num_hidden_layers"], n_heads=hp["num_attention_heads"],
        n_kv_heads=hp["num_key_value_heads"], ffn_dim=hp["intermediate_size"],
        max_seq_len=hp["max_position_embeddings"],
        rope_theta=float(hp["rope_theta"]), norm_eps=float(hp["rms_norm_eps"]),
        param_dtype=jnp.bfloat16,
        **{k: options[k] for k in TRAIN_OPTIONS if options and k in options})


def param_specs(cfg):
    from ray_tpu.models import llama

    return llama.param_specs(cfg)


def init_params(key, cfg):
    from ray_tpu.models import llama

    return llama.init_params(key, cfg)


def loss_fn(params, batch, config):
    from ray_tpu.models import llama

    return llama.loss_fn(params, batch, config)


def logits(params, tokens, config):
    """tokens (B, S) -> float32 logits (B, S, V) through the model code
    ``loss_fn`` runs: what a train cell's ``correct`` compares per token
    with ``reference_logits``."""
    from ray_tpu.models import llama

    return llama.forward(params, tokens, config)


# -- the plain float32 reference (imports nothing of ray_tpu) ----------
# the loss is the mean cross entropy and nothing more, so the harness
# takes it from these logits and the family gives no reference_loss
def reference_logits(params, tokens, hp: dict, last: int = 0):
    return reference.logits(params, tokens, theta=hp["rope_theta"],
                            eps=hp["rms_norm_eps"], last=last)


# -- required work, from shapes alone ----------------------------------
train_flops_per_token = flops.train_flops_per_token
total_params = flops.total_params

# the kernels that must be in the compiled train step's text for
# ``correct``, and whose share of their roofline the family reports:
# kernel_call gives the FLOPs and HBM bytes of ONE call of each
KERNELS = tuple(flops.FLASH_MATMULS)
kernel_call = flops.flash_call

# -- what names an op in a device trace --------------------------------
# the program's jax.named_scope names (models/llama.py, train_step.py,
# the engine's programs): an op under none of them, and no kernel or
# collective by its own name, is what scope_unattributed_pct.* counts
SCOPES = ("embed", "layers", "attn", "mlp", "head", "ce", "loss_and_grad",
          "optimizer", "kv_write", "attn_cached", "kv_slice", "sample")
NAMED_OPS = ("^(flash_attention_|fused_ce|all-gather|all-reduce"
             "|reduce-scatter|collective-permute|all-to-all|async-collective)")
# of them, the scopes that move the KV cache and the scopes that compute
# the model (a cache-shaped result under one of the latter is the
# model's own work): kv_cache_move_share_pct.*
KV_SCOPES = ("kv_slice", "kv_write")
COMPUTE_SCOPES = ("embed", "attn", "mlp", "head", "ce", "sample", "optimizer")
