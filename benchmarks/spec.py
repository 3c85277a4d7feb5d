"""Finding a cell's files by name, and evaluating its metrics.

Nothing here knows a cell, a configuration or a metric by name: a cell
is ``workloads/<cell>.json``, its configuration ``configs/<config>.json``,
a metric ``metrics/<metric>.json`` naming a reader ``readers/<reader>.py``
with ``read(ctx, args) -> number or None``. Which metrics a cell reports
is what ``BENCHMARK.json`` says.
"""

from __future__ import annotations

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def benchmark_json() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def apply_environment() -> None:
    """The process environment the cells run in (environment.json, with
    the reason for each entry); what the machine already sets, stays.
    Before ray_tpu.init(): the workers inherit it."""
    for key, value in load_json("environment.json")["defaults"].items():
        os.environ.setdefault(key, value)


def load_cell(name: str, rehearse: bool) -> dict:
    """The cell's file with its configuration resolved under ``hp``
    (the sizes as run). A cell may override only keys its configuration
    lists under ``reduced``; the rehearsal swaps in the toy presets."""
    cell = load_json("workloads", f"{name}.json")
    hp = load_json("configs", f"{cell['config']}.json")
    overrides = cell.get("config_overrides", {})
    not_allowed = set(overrides) - set(hp.get("reduced", []))
    if not_allowed:
        raise ValueError(
            f"cell {name} overrides {sorted(not_allowed)}, which its "
            "configuration does not list under 'reduced'")
    hp = {**hp, **overrides}
    if rehearse:
        hp = {**hp, **hp["rehearsal"]}
        cell = _merge(cell, cell.get("rehearsal", {}))
    cell["hp"] = hp
    return cell


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(base[k], v) if (
            isinstance(v, dict) and isinstance(base.get(k), dict)) else v
    return out


def cell_metrics(cell_name: str, traced: bool) -> dict:
    """{metric name: its file's dict} for this cell and kind of run, as
    BENCHMARK.json declares them; the unit is BENCHMARK.json's."""
    bench = benchmark_json()
    out = {}
    for entry in bench["per_layer" if traced else "end_to_end"]:
        if "workloads" in entry and cell_name not in entry["workloads"]:
            continue
        metric = load_json("metrics", f"{entry['name']}.json")
        out[entry["name"]] = {**metric, "unit": entry["unit"]}
    return out


def evaluate(metrics: dict, ctx: dict) -> dict:
    """Each metric through its reader; a reader that finds nothing to
    read returns None and the metric is left out."""
    out = {}
    for name, metric in metrics.items():
        reader = importlib.import_module(
            f"benchmarks.readers.{metric['reader']}")
        value = reader.read(ctx, metric.get("args", {}))
        if value is not None:
            out[name] = {"value": float(value), "unit": metric["unit"]}
    return out


def peak_for(kind: str, rehearse: bool) -> dict:
    peaks = load_json("peaks.json")
    if kind not in peaks:
        if rehearse:  # so that the rehearsal reaches the same readers
            return {**next(iter(peaks.values())), "source": "rehearsal"}
        raise KeyError(
            f"no peaks recorded for device_kind {kind!r}; add it to "
            "benchmarks/peaks.json with its source")
    return peaks[kind]


def llama_config(hp: dict, **overrides):
    """The repo's model configuration for these published sizes, bf16
    parameters."""
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
        param_dtype=jnp.bfloat16, **overrides,
    )


def prng_key(seed: int):
    """A key from any whole-number seed (the driver's pass 2**31)."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
