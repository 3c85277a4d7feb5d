"""The ``pangu`` family: decoders that ``models/latent_moe.py`` runs
(openPangu-Ultra-MoE-718B): latent attention over a cache of one
576-wide row a token a layer, four norms a layer, dense leading layers
and then a shared expert beside sigmoid-routed ones of which the chip
holds its share. ``hp`` is the configuration file's dict: the published
config.json keys, and under ``share`` the router's published width and
the ids of the experts held here.

Served only, as the ``mellum`` family is: it gives what "A served
family" of README.md lists and nothing of a trained one; a train cell of
this family fails on the missing name, it is never skipped. The
multi-token-prediction layer of the published model is not built: the
main model's logits do not depend on it (the configuration's ``assumed``
says what serving with it would take).
"""

from __future__ import annotations

from benchmarks.families import pangu_reference


def model_config(hp: dict, options: dict = None):
    """The repo's configuration object for these published sizes, bf16
    parameters (float32 router); serving passes no ``options``."""
    import jax.numpy as jnp

    from ray_tpu.models.latent_moe import LatentMoEConfig

    if options is not None:
        raise ValueError("the pangu family is served only: no train options")
    if not hp["sandwich_norm"] or hp["n_shared_experts"] != 1:
        raise ValueError("models/latent_moe.py has four norms a layer and "
                         "one shared expert")
    if hp["num_nextn_predict_layers"]:
        raise ValueError("no multi-token-prediction layer is built")
    share = hp["share"]
    if len(share["held_experts"]) != hp["n_routed_experts"]:
        raise ValueError(
            f"n_routed_experts {hp['n_routed_experts']} counts the experts "
            f"held here, share.held_experts names "
            f"{len(share['held_experts'])}")
    # bfloat16 as served; the toy rehearsal preset computes in float32
    # (the configuration's file says why)
    dtype = getattr(jnp, hp.get("compute_dtype", "bfloat16"))
    return LatentMoEConfig(
        vocab_size=hp["vocab_size"], dim=hp["hidden_size"],
        n_layers=hp["num_hidden_layers"], n_heads=hp["num_attention_heads"],
        n_kv_heads=hp["num_key_value_heads"],
        ffn_dim=hp["intermediate_size"],
        max_seq_len=hp["max_position_embeddings"],
        rope_theta=float(hp["rope_theta"]),
        norm_eps=float(hp["rms_norm_eps"]), dtype=dtype, param_dtype=dtype,
        remat=False,
        q_rank=hp["q_lora_rank"], kv_rank=hp["kv_lora_rank"],
        nope_dim=hp["qk_nope_head_dim"], rope_dim=hp["qk_rope_head_dim"],
        v_dim=hp["v_head_dim"], n_dense_layers=hp["first_k_dense_replace"],
        n_experts=share["router_experts"],
        experts_per_token=hp["num_experts_per_tok"],
        expert_dim=hp["moe_intermediate_size"],
        held_experts=tuple(share["held_experts"]),
        shared_dim=hp["n_shared_experts"] * hp["moe_intermediate_size"],
        norm_topk_prob=hp["norm_topk_prob"],
        routed_scale=float(hp["routed_scaling_factor"]))


def init_params(key, cfg):
    from ray_tpu.models import latent_moe

    return latent_moe.init_params(key, cfg)


# -- the plain float32 reference (imports nothing of ray_tpu) ----------
def reference_logits(params, tokens, hp: dict, last: int = 0):
    return pangu_reference.logits(params, tokens, hp, last=last)


# -- what names an op in a device trace --------------------------------
# the jax.named_scope names of models/latent_moe.py, ops/moe.py and the
# engine's programs
SCOPES = ("embed", "layers", "attn", "latent_q", "latent_kv", "latent_expand",
          "attn_latent_prefill", "attn_latent_decode", "attn_out", "mlp",
          "moe", "moe_router", "moe_dispatch", "moe_experts", "moe_combine",
          "moe_shared", "head", "kv_write", "kv_slice", "sample")
NAMED_OPS = "^(ragged-dot|all-gather|all-reduce|reduce-scatter|all-to-all)"
KV_SCOPES = ("kv_slice", "kv_write")
COMPUTE_SCOPES = ("embed", "attn", "mlp", "moe", "head", "sample")
