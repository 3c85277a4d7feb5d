"""Required work of the ``pangu`` family's two attention forms, from
what was asked of them and not from what implements them. The counts are
the program's (``models/latent_moe.py``'s device counters, summed over
layers and calls, taken between two snapshots).

*Prefill* (a chunk of rows attends to its sequence's latent rows): a
visible pair (a live query row, a row it attends to) is a head's score
and its weighted value, ``2 * heads * (nope + rope + v)`` FLOPs; each
latent row a call attends to is made into the heads' keys and values
once a call, ``2 * kv_rank * heads * (nope + v)`` FLOPs, and is read
once a call, ``kv_rank + rope`` values. A form that pays the wider
absorbed key at every pair instead does more than this at a chunk's
rows, so the share it reads is lower, as it should be.

*Decode* (one row a lane attends to its lane's latent rows): a row
attended is ``2 * heads * ((kv_rank + rope) + kv_rank)`` FLOPs, all
heads over the one 576-wide key and the one 512-wide value, and
``kv_rank + rope`` values read once for all heads. Folding ``Wuk`` and
``Wuv`` into query and output is a few MFLOP a lane and not counted.
"""

from __future__ import annotations


def prefill_work(hp: dict, pairs: float, rows: float,
                 bytes_per_value: int = 2) -> dict:
    heads = hp["num_attention_heads"]
    nope, rope, v = (hp["qk_nope_head_dim"], hp["qk_rope_head_dim"],
                     hp["v_head_dim"])
    rank = hp["kv_lora_rank"]
    return {
        "flops": (2.0 * heads * (nope + rope + v) * pairs
                  + 2.0 * rank * heads * (nope + v) * rows),
        "bytes": float(bytes_per_value) * (rank + rope) * rows,
    }


def decode_work(hp: dict, rows: float, bytes_per_value: int = 2) -> dict:
    heads, rank, rope = (hp["num_attention_heads"], hp["kv_lora_rank"],
                         hp["qk_rope_head_dim"])
    return {
        "flops": 2.0 * heads * ((rank + rope) + rank) * rows,
        "bytes": float(bytes_per_value) * (rank + rope) * rows,
    }
