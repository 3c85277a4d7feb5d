"""How far a routing flip made on purpose reaches, on the cohere
family's own reference at a cell's own sizes (on the chip, which this
process holds; ``--rehearse``: the toy preset on the CPU):

    python benchmarks/tests/reach_cohere.py <cell> <seeds> [--rehearse]

On each seed (``n:first``) the seeded weights and a seeded sequence of
``ROWS`` tokens go through ``cohere_reference.logits`` as they are and
with ONE flip (``flip=(layer, row)``: at that row of that layer the last
chosen expert and the best one passed over change places; the first row
from ``ROW`` on at which the flip shows, since one between two experts
that are not held here changes nothing), for a flip in the first layer
and one in the third. Read: the relative RMS of the
flipped row's logits against the sound pass's, and of every row behind
it. A row behind the flip whose own choices held moves by what attention
carries of the flipped row; one whose own choice tipped over reads like
a flip itself. What a cell that states its shares presupposes
(benchmarks/README.md, "A served family") is that the first kind moves
by under a tenth of ``rel_rms_tol``. The readings go to stdout and to
``chiprun_out/reach.<cell>.json``."""

import json
import os
import sys
from functools import partial

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.abspath(os.path.join(HERE, *[os.pardir] * 2)))
ROWS, ROW = 1536, 512


def main(argv) -> int:
    import jax
    import numpy as np

    import serving_control as sc
    from benchmarks import spec
    from benchmarks.families import cohere_reference

    rehearse = "--rehearse" in argv
    cell_name, seeds = [a for a in argv if a != "--rehearse"]
    cell = spec.load_cell(cell_name, rehearse)
    hp, tol = cell["hp"], cell["serve"]["reference_check"]["rel_rms_tol"]
    family = spec.family_of(hp)
    cfg = family.model_config(hp)
    rows, row = (ROWS, ROW) if not rehearse else (192, 64)
    out = {}
    for seed in sc.seeds_of(seeds):
        key = spec.prng_key(seed)
        params = jax.jit(partial(family.init_params, cfg=cfg))(key)
        tokens = np.asarray(jax.random.randint(
            jax.random.fold_in(key, 1), (rows,), 0, hp["vocab_size"]))
        sound = np.asarray(cohere_reference.logits(params, tokens, hp))
        for layer in (0, 2):
            # a flip between two experts that are not held here shows
            # nowhere (three flips in four, at an eighth of the experts
            # held): the first of eight rows at which one shows is read
            for at in range(row, row + 8):
                flipped = np.asarray(cohere_reference.logits(
                    params, tokens, hp, flip=(layer, at)))
                moved = np.sqrt(((flipped - sound) ** 2).mean(-1)
                                / (sound ** 2).mean(-1))
                if moved[at] > 0:
                    break
            row = at
            behind = moved[row + 1:]
            held = behind[behind <= tol]
            got = {"row": int(row), "before_the_flip_max": float(moved[:row].max()),
                   "flipped_row": float(moved[row]),
                   "rows_behind": int(behind.size),
                   "rows_behind_over_the_limit": int((behind > tol).sum()),
                   "held_median": float(np.median(held)),
                   "held_p95": float(np.percentile(held, 95)),
                   "held_max": float(held.max()),
                   "held_over_a_tenth_of_the_limit": int(
                       (held > tol / 10).sum())}
            out[f"{seed}.layer{layer}"] = got
            print("reach", seed, "flip in layer", layer, got, flush=True)
        del params
    os.makedirs("chiprun_out", exist_ok=True)
    tag = ".rehearsal" if rehearse else ""
    with open(f"chiprun_out/reach.{cell_name}{tag}.json", "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
