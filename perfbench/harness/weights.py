"""Weights from the seed, made on the device in one jitted call.

A reference module describes its parameters as a tree of ``Leaf`` (shape
and how to draw it) in the layout the program's state uses; ``make_state``
draws every leaf from the seed and wraps the parameters in zeroed AdamW
moments, the training state the program's step takes.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class Leaf:
    shape: tuple
    init: str = "normal"   # normal | ones | zeros | gate_bias
    fan_in: int = 1        # normal: std = 1 / sqrt(fan_in)

    def draw(self, key):
        if self.init == "ones":
            return jnp.ones(self.shape, jnp.float32)
        if self.init == "zeros":
            return jnp.zeros(self.shape, jnp.float32)
        if self.init == "gate_bias":
            # input gates at 0, forget gates at 3 (forget little at start)
            half = self.shape[-1] // 2
            row = jnp.concatenate([jnp.zeros((half,)), jnp.full((half,), 3.0)])
            return jnp.broadcast_to(row, self.shape).astype(jnp.float32)
        return jax.random.normal(key, self.shape, jnp.float32) \
            * (1.0 / np.sqrt(self.fan_in))


def is_leaf(x) -> bool:
    return isinstance(x, Leaf)


def seed_key(seed: int):
    """A PRNG key from any whole number, up to 64 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def draw_params(key, layout) -> Any:
    leaves, treedef = jax.tree.flatten(layout, is_leaf=is_leaf)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef,
                              [lf.draw(k) for lf, k in zip(leaves, keys)])


def adamw_zeros(params):
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)  # noqa: E731
    return {"m": jax.tree.map(zeros, params),
            "v": jax.tree.map(zeros, params),
            "step": jnp.zeros((), jnp.int32)}


def state_maker(layout, shardings=None):
    """Jitted ``seed key -> {"params", "opt"}``, every leaf on the device,
    or placed under ``shardings`` (the same values: the draws do not
    depend on where they land)."""
    def make(key):
        params = draw_params(key, layout)
        return {"params": params, "opt": adamw_zeros(params)}
    return _jit(make, shardings)


def params_maker(layout, shardings=None):
    """Jitted ``seed key -> params`` (the same draws as ``state_maker``),
    under ``shardings`` (a params tree) where given."""
    return _jit(lambda key: draw_params(key, layout), shardings)


def _jit(fn, shardings):
    if shardings is None:
        return jax.jit(fn)
    return jax.jit(fn, out_shardings=shardings)


def shapes(layout) -> dict:
    """{path string: shape} of every parameter."""
    flat = jax.tree_util.tree_flatten_with_path(layout, is_leaf=is_leaf)[0]
    return {jax.tree_util.keystr(p): lf.shape for p, lf in flat}
