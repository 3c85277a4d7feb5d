"""The ``deepseek`` family: decoders that ``models/latent_moe.py`` runs
with an indexer beside every layer's latent attention
(DeepSeek-V3.2-Exp): every row scores the cache's rows by a small
multi-head index and attends to the ``index_topk`` of largest score
alone, the cache keeps an index key a row beside the latent row; two
norms a layer, a YaRN rotary table; a dense leading layer and then a
shared expert beside sigmoid-routed ones, chosen under a selection bias
among the best groups, of which the chip holds its share. ``hp`` is the
configuration file's dict: the published config.json keys, and under
``share`` the router's published width and the ids of the experts held
here.

Served only, as the ``pangu`` family is: it gives what "A served
family" of README.md lists and nothing of a trained one. The
multi-token-prediction layer is not built (the configuration's
``assumed`` says what serving with it would take).
"""

from __future__ import annotations

from benchmarks.families import deepseek_reference


def model_config(hp: dict, options: dict = None):
    """The repo's configuration object for these published sizes, bf16
    parameters (float32 routers); serving passes no ``options``."""
    import jax.numpy as jnp

    from ray_tpu.models.latent_moe import LatentMoEConfig
    from ray_tpu.models.rope import YarnRope

    if options is not None:
        raise ValueError("the deepseek family is served only: no train "
                         "options")
    if (hp["n_shared_experts"] != 1 or hp["scoring_func"] != "sigmoid"
            or hp["topk_method"] != "noaux_tc" or hp["moe_layer_freq"] != 1):
        raise ValueError("models/latent_moe.py routes by sigmoid scores "
                         "under a selection bias in every layer behind the "
                         "dense ones, beside one shared expert")
    if hp["num_nextn_predict_layers"]:
        raise ValueError("no multi-token-prediction layer is built")
    share = hp["share"]
    if len(share["held_experts"]) != hp["n_routed_experts"]:
        raise ValueError(
            f"n_routed_experts {hp['n_routed_experts']} counts the experts "
            f"held here, share.held_experts names "
            f"{len(share['held_experts'])}")
    rope, mscale = deepseek_reference.rope_of(hp)
    theta, factor, original, beta_fast, beta_slow = rope
    # bfloat16 as served; the toy rehearsal preset says its own
    dtype = getattr(jnp, hp.get("compute_dtype", "bfloat16"))
    return LatentMoEConfig(
        vocab_size=hp["vocab_size"], dim=hp["hidden_size"],
        n_layers=hp["num_hidden_layers"], n_heads=hp["num_attention_heads"],
        n_kv_heads=hp["num_key_value_heads"],
        ffn_dim=hp["intermediate_size"],
        max_seq_len=hp["max_position_embeddings"],
        rope_theta=theta, norm_eps=float(hp["rms_norm_eps"]), dtype=dtype,
        param_dtype=dtype, remat=False,
        q_rank=hp["q_lora_rank"], kv_rank=hp["kv_lora_rank"],
        nope_dim=hp["qk_nope_head_dim"], rope_dim=hp["qk_rope_head_dim"],
        v_dim=hp["v_head_dim"], n_dense_layers=hp["first_k_dense_replace"],
        n_experts=share["router_experts"],
        experts_per_token=hp["num_experts_per_tok"],
        expert_dim=hp["moe_intermediate_size"],
        held_experts=tuple(share["held_experts"]),
        shared_dim=hp["n_shared_experts"] * hp["moe_intermediate_size"],
        norm_topk_prob=hp["norm_topk_prob"],
        routed_scale=float(hp["routed_scaling_factor"]),
        sandwich_norm=False,
        index_heads=hp["index_n_heads"], index_dim=hp["index_head_dim"],
        index_topk=hp["index_topk"],
        n_groups=hp["n_group"], groups_kept=hp["topk_group"],
        selection_bias=True,
        # cos and sin as they are: mscale = mscale_all_dim, and the
        # factor goes into the scores' scale, squared
        yarn=None if factor is None else YarnRope(
            theta=theta, factor=factor, original_max_position=int(original),
            beta_fast=beta_fast, beta_slow=beta_slow, attention_factor=1.0),
        score_mscale=mscale)


def init_params(key, cfg):
    from ray_tpu.models import latent_moe

    return latent_moe.init_params(key, cfg)


# -- the plain float32 reference (imports nothing of ray_tpu) ----------
# A pass's programs are compiled for its length, half a minute of the
# chip's host for each new one, and a cell's served requests come in
# dozens of lengths: a sequence longer than a quarter of the first of
# these is padded on, behind its tokens, to the next of them, so that
# two sets of programs serve whatever a window finished (the longest a
# cell of this family serves is 24 576 + 512 tokens; a longer one, and a
# toy's, runs at its own length).
PASS_LENGTHS = (12800, 25088)


def reference_logits(params, tokens, hp: dict, last: int = 0):
    import numpy as np

    _WHOLE_PASS.clear()     # its rows are a few GB at the cell's probe
    n = len(tokens)
    total = min((b for b in PASS_LENGTHS if b >= n), default=n)
    if n * 4 <= PASS_LENGTHS[0] or total == n:
        return deepseek_reference.logits(params, tokens, hp, last=last)
    padded = np.zeros(total, np.int32)
    padded[:n] = np.asarray(tokens)
    return deepseek_reference.logits(params, padded, hp, last=last, real=n)


# the last whole pass under choices (``deepseek_reference.logits``' ``memo``)
_WHOLE_PASS: dict = {}


def reference_routed(params, tokens, hp: dict, choices, last: int = 0):
    """The reference under the engine's own choices (README.md, "Under
    the engine's own routing choices"). This family has two kinds of
    discrete choice and ``models/latent_moe.py`` ``read_choices`` says
    both: ``choices`` (layers + 1, S, W) int32 holds every layer's
    selected rows as bits and, last, every routed layer's experts side
    by side; ``margin`` (layers + routed layers, S) judges each
    (``deepseek_reference``'s docstring has both measures, and what
    makes the passes after the first cheap)."""
    return deepseek_reference.logits(params, tokens, hp, last=last,
                                     choices=choices, memo=_WHOLE_PASS)


# -- what names an op in a device trace --------------------------------
# the jax.named_scope names of models/latent_moe.py, ops/moe.py and the
# engine's programs
SCOPES = ("embed", "layers", "attn", "latent_q", "latent_kv", "latent_expand",
          "attn_index", "index_q", "index_k", "index_score", "index_select",
          "attn_latent_prefill", "attn_latent_decode", "attn_out", "mlp",
          "moe", "moe_router", "moe_dispatch", "moe_experts", "moe_combine",
          "moe_shared", "head", "kv_write", "kv_slice", "sample")
NAMED_OPS = "^(ragged-dot|all-gather|all-reduce|reduce-scatter|all-to-all)"
KV_SCOPES = ("kv_slice", "kv_write")
COMPUTE_SCOPES = ("embed", "attn", "mlp", "moe", "head", "sample")
