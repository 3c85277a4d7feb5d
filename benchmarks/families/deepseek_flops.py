"""Required work of the ``deepseek`` family's new pieces, from what was
asked of them and not from what implements them. Every layer asks for
three things of a call's rows:

*The indexer's score* (``index_work``): every (live query row, cache
row at or before it) pair is ``index_n_heads`` products ``index_head_dim``
deep, ``2 * Hi * di`` FLOPs, and every cache row a call scores against
is read once a call (once a lane in a decode), ``di`` values (256 B in
bf16). The ``relu``, the heads' weights and the sum are not counted.

*The selection* is asked for no FLOPs: it orders numbers. Its share of
the device's time is read, no roofline.

*Attention over the selected rows.* A chunk's row (``selected_prefill_
work``) attends to its ``min(position + 1, index_topk)`` rows: a pair
is a head's score and its weighted value, ``2 * heads * (nope + rope +
v)`` FLOPs; the latent rows the call's rows may select from are read
once a call, ``kv_rank + rope`` values each. Making keys and values from
latent rows is a way to do it and not counted (``pangu_flops`` counts
it for a family whose every row is attended to; here a row nobody
selected need not be expanded). A decode lane (``selected_decode_work``)
attends to its selected rows in the absorbed form, ``2 * heads * ((kv_rank
+ rope) + kv_rank)`` FLOPs and ``kv_rank + rope`` values a row, as
``pangu_flops.decode_work`` counts a row attended.
"""

from __future__ import annotations


def index_work(hp: dict, pairs: float, rows: float,
               bytes_per_value: int = 2) -> dict:
    heads, dim = hp["index_n_heads"], hp["index_head_dim"]
    return {"flops": 2.0 * heads * dim * pairs,
            "bytes": float(bytes_per_value) * dim * rows}


def selected_prefill_work(hp: dict, pairs: float, rows: float,
                          bytes_per_value: int = 2) -> dict:
    heads = hp["num_attention_heads"]
    nope, rope, v = (hp["qk_nope_head_dim"], hp["qk_rope_head_dim"],
                     hp["v_head_dim"])
    return {"flops": 2.0 * heads * (nope + rope + v) * pairs,
            "bytes": float(bytes_per_value) * (hp["kv_lora_rank"] + rope)
            * rows}


def selected_decode_work(hp: dict, rows: float,
                         bytes_per_value: int = 2) -> dict:
    heads, rank, rope = (hp["num_attention_heads"], hp["kv_lora_rank"],
                         hp["qk_rope_head_dim"])
    return {"flops": 2.0 * heads * ((rank + rope) + rank) * rows,
            "bytes": float(bytes_per_value) * (rank + rope) * rows}
