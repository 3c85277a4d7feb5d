"""A second family, for the benchmark's own tests only: it proves that a
family is found by name and that no harness file needs to know it. Its
training side is ``models/moe_llama.py`` (a dropless top-2-of-4 mixture
of experts) against the float32 reference beside this file; the engine
serves only what ``models/llama.py`` describes, so its serving side is
that decoder under this second name, against the same reference's dense
branch. No cell of BENCHMARK.json uses it."""

from benchmarks.families import toy_moe_reference as reference


def model_config(hp, options=None):
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.models.moe_llama import MoELlamaConfig

    sizes = dict(
        vocab_size=hp["vocab_size"], dim=hp["hidden_size"],
        n_layers=hp["num_hidden_layers"], n_heads=hp["num_attention_heads"],
        n_kv_heads=hp["num_key_value_heads"], ffn_dim=hp["intermediate_size"],
        max_seq_len=hp["max_position_embeddings"],
        rope_theta=float(hp["rope_theta"]), norm_eps=float(hp["rms_norm_eps"]),
        param_dtype=jnp.bfloat16)
    if options is None:     # serving: the engine's decoder
        return LlamaConfig(**sizes)
    experts, top_k = hp["num_local_experts"], hp["num_experts_per_tok"]
    if "capacity_factor" in MoELlamaConfig.__dataclass_fields__:
        # a program that still drops tokens over a capacity: room for
        # every token at one expert, so nothing is dropped, as in the
        # reference
        sizes["capacity_factor"] = experts / top_k
    return MoELlamaConfig(
        **sizes, remat=options["remat"],
        attention_impl=options["attention_impl"], n_experts=experts,
        experts_per_token=top_k, router_aux_coeff=hp["router_aux_loss_coef"])


def _model(cfg):
    from ray_tpu.models import llama, moe_llama

    return moe_llama if hasattr(cfg, "n_experts") else llama


def param_specs(cfg):
    return _model(cfg).param_specs(cfg)


def init_params(key, cfg):
    return _model(cfg).init_params(key, cfg)


def loss_fn(params, batch, config):
    return _model(config).loss_fn(params, batch, config)


def logits(params, tokens, config):
    out = _model(config).forward(params, tokens, config)
    return out[0] if isinstance(out, tuple) else out    # (logits, aux, ...)


# the loss carries the router's load-balance term besides the mean cross
# entropy, so the family gives its own reference_loss for the first-loss
# check; the per-token check covers the cross-entropy part
reference_loss = reference.loss
reference_logits = reference.logits


def _matmul_params(hp):
    d, hd = hp["hidden_size"], hp["head_dim"]
    h, kv = hp["num_attention_heads"], hp["num_key_value_heads"]
    active = 3 * d * hp["intermediate_size"] * hp["num_experts_per_tok"]
    per_layer = 2 * d * h * hd + 2 * d * kv * hd + active \
        + d * hp["num_local_experts"]
    return hp["num_hidden_layers"] * per_layer + d * hp["vocab_size"]


def train_flops_per_token(hp, seq):
    attn_fwd = 2 * 2 * (seq + 1) / 2 * hp["num_attention_heads"] * hp["head_dim"]
    return 6.0 * _matmul_params(hp) + 3.0 * attn_fwd * hp["num_hidden_layers"]


def total_params(hp):
    d, experts = hp["hidden_size"], hp["num_local_experts"]
    idle = 3 * d * hp["intermediate_size"] * (
        experts - hp["num_experts_per_tok"]) * hp["num_hidden_layers"]
    return _matmul_params(hp) + idle + hp["vocab_size"] * d \
        + hp["num_hidden_layers"] * 2 * d + d


KERNELS = ()        # attention_impl "xla": no kernel to find or to rate


def kernel_call(kernel, batch, seq, hp):
    raise KeyError(kernel)


SCOPES = ("embed", "layers", "attn", "mlp", "head", "loss_and_grad",
          "optimizer", "kv_write", "attn_cached", "sample")
NAMED_OPS = "^(all-gather|all-reduce|reduce-scatter|all-to-all)"
KV_SCOPES = ("kv_write",)
COMPUTE_SCOPES = ("embed", "attn", "mlp", "head", "sample", "optimizer")
