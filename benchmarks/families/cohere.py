"""The ``cohere`` family: decoders that ``models/parallel_moe.py`` runs
(command-a-plus-05-2026, ``model_type`` cohere2_moe): a parallel block
(one LayerNorm feeding grouped-query attention and the experts side by
side), sliding-window layers turned over adjacent pairs beside full
layers with no position term, sigmoid-routed experts of which the chip
holds its share beside several shared experts averaged, and a tied head.
``hp`` is the configuration file's dict: the published config.json keys,
and under ``share`` the router's published width and the ids of the
experts held here.

Served only, as the ``mellum`` and ``pangu`` families are: it gives what
"A served family" of README.md lists and nothing of a trained one; a
train cell of this family fails on the missing name, it is never
skipped. The vision tower of the published model is not built (the
configuration's ``assumed`` says so).
"""

from __future__ import annotations

from benchmarks.families import cohere_reference

LAYER_KINDS = {"sliding_attention": "sliding", "full_attention": "full"}


def model_config(hp: dict, options: dict = None):
    """The repo's configuration object for these published sizes, bf16
    parameters (float32 router); serving passes no ``options``."""
    import jax.numpy as jnp

    from ray_tpu.models.parallel_moe import ParallelMoEConfig

    if options is not None:
        raise ValueError("the cohere family is served only: no train options")
    for key, want in (("use_parallel_block", True), ("use_qk_norm", False),
                      ("expert_selection_fn", "sigmoid"),
                      ("shared_expert_combination_strategy", "average"),
                      ("position_embedding_type", "rope_gptj"),
                      ("rotary_pct", 1), ("first_k_dense_replace", 0),
                      ("tie_word_embeddings", True),
                      ("attention_bias", False)):
        if hp[key] != want:
            raise ValueError(
                f"models/parallel_moe.py has {key} {want!r}, not {hp[key]!r}")
    share = hp["share"]
    if len(share["held_experts"]) != hp["num_experts"]:
        raise ValueError(
            f"num_experts {hp['num_experts']} counts the experts held here, "
            f"share.held_experts names {len(share['held_experts'])}")
    layers = hp["num_hidden_layers"]
    # bfloat16 as served; the toy rehearsal preset computes in float32
    # (the configuration's file says why)
    dtype = getattr(jnp, hp.get("compute_dtype", "bfloat16"))
    return ParallelMoEConfig(
        vocab_size=hp["vocab_size"], dim=hp["hidden_size"], n_layers=layers,
        n_heads=hp["num_attention_heads"],
        n_kv_heads=hp["num_key_value_heads"], head_size=hp["head_dim"],
        ffn_dim=0,                          # no dense layer
        max_seq_len=hp["max_position_embeddings"],
        rope_theta=float(hp["rope_theta"]),
        norm_eps=float(hp["layer_norm_eps"]), dtype=dtype, param_dtype=dtype,
        remat=False,
        layer_types=tuple(LAYER_KINDS[t] for t in hp["layer_types"][:layers]),
        sliding_window=hp["sliding_window"],
        n_experts=share["router_experts"],
        experts_per_token=hp["num_experts_per_tok"],
        expert_dim=hp["intermediate_size"],
        norm_topk_prob=hp["norm_topk_prob"],
        held_experts=tuple(share["held_experts"]),
        n_shared_experts=hp["num_shared_experts"],
        logit_scale=float(hp["logit_scale"]))


def init_params(key, cfg):
    from ray_tpu.models import parallel_moe

    return parallel_moe.init_params(key, cfg)


# -- the plain float32 reference (imports nothing of ray_tpu) ----------
def reference_logits(params, tokens, hp: dict, last: int = 0):
    return cohere_reference.logits(params, tokens, hp, last=last)


# -- what names an op in a device trace --------------------------------
# the jax.named_scope names of models/parallel_moe.py, window_moe.py,
# ops/moe.py and the engine's programs
SCOPES = ("embed", "layers", "block_norm", "attn", "moe", "moe_router",
          "moe_dispatch", "moe_experts", "moe_combine", "moe_shared", "head",
          "kv_write", "attn_cached", "attn_window", "kv_slice", "sample")
NAMED_OPS = ("^(chunk_attention|ragged-dot|all-gather|all-reduce|"
             "reduce-scatter|all-to-all)")
KV_SCOPES = ("kv_slice", "kv_write")
COMPUTE_SCOPES = ("embed", "block_norm", "attn", "moe", "head", "sample")
