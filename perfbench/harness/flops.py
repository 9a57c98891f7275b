"""The FLOP count ``mfu`` divides by, kept with the benchmark.

``model_flops`` copies the program's convention (``models/model.py``,
``model_flops``): 6 x non-embedding parameters x tokens for a training
step, where the embedding and the output head (paths holding ``emb`` or
``lm_head``) do not count.  Attention's score arithmetic is not counted
either, so ``mfu`` understates the work of long sequences.
"""
from __future__ import annotations

import math


def param_counts(shapes: dict) -> dict:
    """``shapes``: {path string: shape tuple} of the model's parameters."""
    total = embed = 0
    for path, shape in shapes.items():
        n = math.prod(shape)
        total += n
        if "emb" in path or "lm_head" in path:
            embed += n
    return {"total": total, "embed": embed, "non_embed": total - embed}


def model_flops(shapes: dict, tokens: int) -> float:
    """Training FLOPs of ``tokens`` tokens: 6 * N_non_embedding * tokens."""
    return 6.0 * param_counts(shapes)["non_embed"] * tokens
