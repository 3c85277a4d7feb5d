"""LLM deployment configuration + TP x PP placement sizing.

Parity: python/ray/llm/_internal/serve/deployments/llm/vllm/
vllm_models.py:123-142 — the reference sizes a placement group from the
engine's tensor/pipeline parallelism (PACK when pp==1, SPREAD with one
bundle per pp rank otherwise). Here the framework owns that natively:
``placement_bundles()`` returns the bundles + strategy the serve
deployment (or a batch-inference actor pool) reserves.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple


@dataclass
class LLMConfig:
    """Declarative model+engine config for serving / batch inference."""

    model_id: str = "base"            # name openai-style bodies use for
    # the base model ({"model": model_id} routes to base, not a LoRA)
    model_config: Any = None          # a model's configuration object
    # (models.llama.LlamaConfig, models.window_moe.WindowMoEConfig): its
    # ``model_module`` names the module that initialises and serves it
    checkpoint_path: Optional[str] = None  # orbax/npz dir; None = random init
    tensor_parallel_size: int = 1
    pipeline_parallel_size: int = 1
    max_batch_size: int = 8
    max_seq_len: int = 512
    accelerator_type: str = "TPU"
    # passed to LlamaEngine (max_slots=...). The prefill chunk is not
    # among what a deployment sets: the engine takes the roofline's
    # ridge of its chip (FLOPs per HBM byte, times the weights' bytes
    # per parameter over 2), over the share of a call's rows that its
    # model's heaviest weights see, as a rule of thumb: 256 rows of bf16
    # for a dense model and 2048 for experts of which a row meets 8 of
    # 64, on a v5e, the one chip it was measured on. The server runs
    # every engine program once (LlamaEngine.warm_up) before it takes a
    # request
    engine_kwargs: Dict[str, Any] = field(default_factory=dict)
    # LoRA multiplexing (reference: ray.llm LoraConfig):
    #   {"dynamic_lora_loading_path": dir with <adapter_id>.npz,
    #    "max_adapters_per_replica": 4, "scale": 1.0}
    lora_config: Optional[Dict[str, Any]] = None

    def placement_bundles(self) -> Tuple[List[Dict[str, float]], str]:
        """(bundles, strategy): one bundle of tp chips per pp rank.

        pp == 1  -> single PACK bundle with tp chips (one host, ICI).
        pp  > 1  -> SPREAD, one tp-chip bundle per pipeline stage —
        stages ride DCN between hosts, tensor parallelism stays on-host
        ICI (the reference's PACK-vs-SPREAD split, vllm_models.py:131).
        """
        tp = self.tensor_parallel_size
        pp = self.pipeline_parallel_size
        res_key = self.accelerator_type if self.accelerator_type else "TPU"
        if pp == 1:
            return [{res_key: float(tp), "CPU": 1.0}], "PACK"
        return (
            [{res_key: float(tp), "CPU": 1.0} for _ in range(pp)],
            "SPREAD",
        )

    def resolved_model_config(self):
        """``model_config``, or the toy llama where none is given."""
        if self.model_config is not None:
            return self.model_config
        from ray_tpu.models import llama

        return llama.LLAMA_TINY

    def load_params(self):
        """Materialize model params: from checkpoint_path if given
        (orbax dir or .npz), else fresh initialization — one jitted
        program that writes every leaf in the configuration's
        param_dtype, not an eager float32 op per leaf."""
        import importlib
        from functools import partial

        import jax

        cfg = self.resolved_model_config()
        model = importlib.import_module(cfg.model_module)
        init = jax.jit(partial(model.init_params, config=cfg))
        if not self.checkpoint_path:
            return init(jax.random.PRNGKey(0))
        import os

        if self.checkpoint_path.endswith(".npz"):
            import numpy as np

            flat = dict(np.load(self.checkpoint_path))
            return _unflatten(flat)
        # orbax checkpoint dir (the Train stack's format,
        # train/_checkpoint.py)
        import orbax.checkpoint as ocp

        target = init(jax.random.PRNGKey(0))
        ckptr = ocp.StandardCheckpointer()
        return ckptr.restore(os.path.abspath(self.checkpoint_path), target)


def save_params_npz(params, path: str) -> None:
    """Flat .npz export (portable mini-format for tests/examples)."""
    import numpy as np

    flat = _flatten(params)
    np.savez(path, **{k: np.asarray(v) for k, v in flat.items()})


def _flatten(tree, prefix=""):
    out = {}
    if isinstance(tree, dict):
        for k, v in tree.items():
            out.update(_flatten(v, f"{prefix}{k}/"))
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            out.update(_flatten(v, f"{prefix}{i}/"))
    else:
        out[prefix[:-1]] = tree
    return out


def _unflatten(flat):
    root: dict = {}
    for key, value in flat.items():
        parts = key.split("/")
        node = root
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = value
    return _listify(root)


def _listify(node):
    if not isinstance(node, dict):
        return node
    if node and all(k.isdigit() for k in node):
        return [_listify(node[k]) for k in sorted(node, key=int)]
    return {k: _listify(v) for k, v in node.items()}
