"""The training comparison: the program's first steps against the plain
reference from the same weights and rows.

Three numbers, each the worst over its parts:

  loss_gap    each of the first steps' loss, |program - reference| over
              |reference|;
  grad_gap    per parameter leaf, the norm of the first gradient as the
              optimizer gets it (clipped), which the program's state holds
              as m_1 / (1 - b1); the gap of the two norms over the larger
              of the reference leaf's norm and the median leaf's;
  change_gap  the same for the norm of each leaf's change over the steps,
              leaving out leaves whose reference gradient is under a
              thousandth of the median leaf's (their change is round-off
              under Adam).

The reference's AdamW is written out here from its definition, with the
traffic file's hyper-parameters; ``quant`` selects the reference itself
("f32") or the control ("fp8": every matmul operand rounded to float8
e4m3 in the forward pass and its gradient to e5m2 in the backward pass,
each with one per-tensor scale).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: gradients whose norm is under this share of the median leaf's are
#: round-off; their leaves are left out of the change
ROUNDOFF_SHARE = 1e-3


def _round(x, dtype):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0)
    return (x / scale).astype(dtype).astype(x.dtype) * scale


@jax.custom_vjp
def fp8(x):
    return _round(x, jnp.float8_e4m3fn)


def _fp8_fwd(x):
    return fp8(x), None


def _fp8_bwd(_, g):
    return (_round(g, jnp.float8_e5m2),)


fp8.defvjp(_fp8_fwd, _fp8_bwd)

QUANT = {"f32": lambda x: x, "fp8": fp8}


@jax.jit
def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def adamw(params, m, v, grads, t, h):
    """One AdamW step with global-norm clipping; ``t`` counts from 1."""
    leaves = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
    scale = jnp.minimum(1.0, h["clip_norm"] / jnp.maximum(gnorm, 1e-12))
    g = jax.tree.map(lambda x: x * scale, grads)
    m = jax.tree.map(lambda a, b: h["b1"] * a + (1 - h["b1"]) * b, m, g)
    v = jax.tree.map(lambda a, b: h["b2"] * a + (1 - h["b2"]) * b * b, v, g)
    c1 = 1 - h["b1"] ** t
    c2 = 1 - h["b2"] ** t
    params = jax.tree.map(
        lambda p, a, b: p - h["lr"] * ((a / c1) / (jnp.sqrt(b / c2) + h["eps"])
                                       + h["weight_decay"] * p),
        params, m, v)
    return params, m, v, g


def reference_steps(ref, config: dict, h: dict, quant: str = "f32"):
    """``(params, m, v, t, tokens) -> (params, m, v, loss, clipped-gradient
    leaf norms)`` of the reference, ``tokens`` a host array.  The loss and
    its gradient are taken one row at a time and averaged: every row has
    the same positions, so that is the batch's mean, and a batch that the
    chip cannot hold at once in float32 still fits."""
    q = QUANT[quant]

    @jax.jit
    def row_grad(params, row):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(ref.loss)(params, row, config, q)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def update(params, m, v, grads, t, rows):
        with jax.default_matmul_precision("highest"):
            grads = jax.tree.map(lambda x: x / rows, grads)
            params, m, v, g = adamw(params, m, v, grads, t, h)
        return params, m, v, leaf_norms(g)

    def step(params, m, v, t, tokens):
        n = tokens.shape[0]
        loss, grads = 0.0, None
        for r in range(n):
            lr, gr = row_grad(params, jnp.asarray(tokens[r:r + 1]))
            loss += float(lr)
            grads = gr if grads is None else _add(grads, gr)
            del gr
        params, m, v, gn = update(params, m, v, grads, t, jnp.float32(n))
        return params, m, v, loss / n, gn

    return step


@functools.partial(jax.jit, donate_argnums=(0,))
def _add(a, b):
    return jax.tree.map(jnp.add, a, b)


@jax.jit
def change_norms(params, params0):
    return leaf_norms(jax.tree.map(lambda a, b: a - b, params, params0))


def program_readings(one, state, n: int, b1: float, initial_params):
    """Drive the program's first ``n`` steps through ``one(state) -> (state,
    snap, loss)`` and read what the reference is compared on: each loss,
    the first gradient's leaf norms from the optimizer's state after step 1
    (m_1 / (1 - b1)) and the leaf norms of the parameters' change after
    step ``n``, from the state that step ``n + 1`` would take."""
    prog = {"losses": []}
    snap = None
    for i in range(n):
        snap = None   # not held through the next step
        state, snap, loss = one(state)
        prog["losses"].append(loss)
        if i == 0:
            prog["grad_norms"] = np.asarray(
                leaf_norms(state["opt"]["m"]), np.float64) / (1.0 - b1)
    prog["change_norms"] = np.asarray(
        change_norms(state["params"], initial_params()), np.float64)
    return state, snap, prog


def run_reference(ref, config: dict, h: dict, make_params, key, batches,
                  quant: str = "f32") -> dict:
    """The reference's readings over ``batches`` (host token arrays)."""
    step = reference_steps(ref, config, h, quant)
    params = make_params(key)
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)  # noqa: E731
    m = jax.tree.map(zeros, params)
    v = jax.tree.map(zeros, params)
    losses, grad_norms = [], None
    for t, tokens in enumerate(batches, start=1):
        params, m, v, loss, gn = step(params, m, v, jnp.float32(t), tokens)
        losses.append(loss)
        if grad_norms is None:
            grad_norms = np.asarray(gn, np.float64)
    del m, v
    change = np.asarray(change_norms(params, make_params(key)), np.float64)
    del params
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}


def leaf_gaps(prog, ref, keep=None) -> np.ndarray:
    """Per leaf, |program norm - reference norm| over the larger of the
    reference leaf's norm and the median leaf's (kept leaves only)."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if keep is None:
        keep = np.ones(ref.shape, bool)
    den = np.maximum(ref, float(np.median(ref[keep])))
    return (np.abs(prog - ref) / den)[keep]


def gaps(prog: dict, ref: dict) -> dict:
    """Every number a cell may compare; its limits file names the ones it
    does.  ``*_median`` is the median leaf's gap, for a model whose worst
    leaf is round-off noise at its stated precision."""
    lp = np.asarray(prog["losses"], np.float64)
    lr = np.asarray(ref["losses"], np.float64)
    g = ref["grad_norms"]
    keep = g >= ROUNDOFF_SHARE * np.median(g)
    grad = leaf_gaps(prog["grad_norms"], g)
    change = leaf_gaps(prog["change_norms"], ref["change_norms"], keep)
    return {"loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
            "grad_gap": float(np.max(grad)),
            "change_gap": float(np.max(change)),
            "grad_gap_median": float(np.median(grad)),
            "change_gap_median": float(np.median(change))}
