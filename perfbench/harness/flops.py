"""The FLOP count ``mfu`` divides by, kept with the benchmark.

``model_flops`` copies the program's convention (``models/model.py``,
``model_flops``): 6 x non-embedding parameters x tokens for a training
step, where the embedding and the output head (paths holding ``emb`` or
``lm_head``) do not count.  Attention's score arithmetic is not counted
either, so ``mfu`` understates the work of long sequences.  A parameter
counts by the share of it that a token uses: all of it, unless the
reference states a smaller share (``active_shares``), as for routed
experts, of which a token uses its top-k.
"""
from __future__ import annotations

import math


def _embedding(path: str) -> bool:
    return "emb" in path or "lm_head" in path


def param_counts(shapes: dict) -> dict:
    """``shapes``: {path string: shape tuple} of the model's parameters."""
    total = embed = 0
    for path, shape in shapes.items():
        n = math.prod(shape)
        total += n
        if _embedding(path):
            embed += n
    return {"total": total, "embed": embed, "non_embed": total - embed}


def model_flops(shapes: dict, tokens: int, active: dict | None = None
                ) -> float:
    """Training FLOPs of ``tokens`` tokens: 6 * N_active_non_embedding *
    tokens, where ``active`` maps a path to the share of that parameter
    a token uses (1 for every path it does not name)."""
    active = active or {}
    n = sum(math.prod(shape) * active.get(path, 1)
            for path, shape in shapes.items() if not _embedding(path))
    return 6.0 * n * tokens
