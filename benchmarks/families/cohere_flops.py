"""Required work of the ``cohere`` family's chunk-form attention, from
the equations and not from what implements them: a floor under any
implementation, so no later kernel reads over 100 %.

A chunk call's ``n`` live rows stand at positions ``start .. start + n
- 1`` of their sequence. In a **full** layer row t attends to every row
``s <= t``: ``n x start + n (n + 1) / 2`` visible (query, key) pairs,
over the ``start + n`` key and value rows the call attends to. In a
**sliding** layer of window W row t attends to ``min(t + 1, W)`` rows:
the pairs are their sum, over the ``start + n - max(0, start - W + 1)``
rows the call's windows cover. A visible pair is one score and one
weighted value in each of the query heads: ``4 x heads x head_dim``
FLOPs. Each key and value row attended is read once a key/value head
(``2 x kv_heads x head_dim`` values: the query heads of a group share
it), the queries come in and the result goes out once (``2 x heads x
head_dim`` values a live row). Rows a padded chunk carries behind its
tokens are nobody's and ask for nothing.
"""

from __future__ import annotations


def chunk_pairs_and_rows(n: int, start: int, window=None) -> tuple:
    """(visible (query, key) pairs, key rows attended) of one layer for
    ``n`` live rows from position ``start``; ``window`` None: a full
    layer."""
    if window is None:
        return n * start + n * (n + 1) // 2, start + n
    pairs = sum(min(start + t + 1, window) for t in range(n))
    return pairs, start + n - max(0, start - window + 1)


def chunk_attention_work(hp: dict, calls, bytes_per_value: int = 2) -> dict:
    """FLOPs and HBM bytes of the attention of the chunk calls ``calls``
    ([(live rows, the row they start at)]) over every layer of ``hp``."""
    heads, kv_heads, hd = (hp["num_attention_heads"],
                           hp["num_key_value_heads"], hp["head_dim"])
    kinds = hp["layer_types"][:hp["num_hidden_layers"]]
    windows = [hp["sliding_window"] if k == "sliding_attention" else None
               for k in kinds]
    pairs = attended = live = 0
    for n, start in calls:
        for window in windows:
            p, r = chunk_pairs_and_rows(n, start, window)
            pairs, attended = pairs + p, attended + r
        live += n * len(windows)
    return {
        "flops": 4.0 * heads * hd * pairs,
        "bytes": float(bytes_per_value) * hd * (
            2 * kv_heads * attended + 2 * heads * live),
    }
