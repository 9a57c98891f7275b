"""Faults planted in the program underneath a run, to show that ``correct``
catches them, and the training cells' lower-precision control: the
harness's tests and ``calibrate.py`` use them, the benchmark's runs never
do.  Each is a context manager that patches a module attribute the loops
look up at call time."""
from __future__ import annotations

import contextlib
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np


@contextlib.contextmanager
def state_unchanged():
    """The step returns the state it was given (and a copy as its
    snapshot), with the loss it computed."""
    from repro.train import steps

    real = steps.make_train_step

    def make(cfg, *, lr=3e-4, capture=False):
        step = real(cfg, lr=lr, capture=capture)

        def stale(state, batch):
            out = step(state, batch)
            keep = jax.tree.map(lambda x: x + jnp.zeros((), x.dtype), state)
            return (keep, keep, out[-1]) if capture else (keep, out[-1])
        return stale

    with mock.patch.object(steps, "make_train_step", make):
        yield


@contextlib.contextmanager
def half_batch():
    """The loss leaves out half of the batch and takes the mean over the
    rest: half of the rows, or with one row the second half of its
    positions."""
    from repro.models import transformer as TF
    from repro.train import steps

    def make_loss_fn(cfg):
        def loss(params, batch):
            tok = batch["tokens"]
            b, t = tok.shape
            tok = tok[: b // 2] if b >= 2 else tok[:, : t // 2]
            return TF.lm_loss(params, cfg, {"tokens": tok})
        return loss

    with mock.patch.object(steps, "make_loss_fn", make_loss_fn):
        yield


@contextlib.contextmanager
def stored_byte_flipped():
    """Every file tier write lands with one byte of its payload flipped."""
    from repro.core import storage

    real = storage.FileTier.put

    def put(self, key, data):
        data = bytearray(data)
        data[len(data) // 2] ^= 0x40
        return real(self, key, bytes(data))

    with mock.patch.object(storage.FileTier, "put", put):
        yield


def _restored_through(transform):
    from repro.core import api

    real = api.VelocClient.restart_latest

    def restart_latest(self, template, shardings=None):
        v, state = real(self, template, shardings)
        return v, (None if state is None else transform(state))

    return mock.patch.object(api.VelocClient, "restart_latest",
                             restart_latest)


@contextlib.contextmanager
def restored_bf16():
    """The restore hands back its state rounded to bfloat16 (the resume
    cell's lower-precision control)."""
    def bf16(state):
        return jax.tree.map(
            lambda x: x.astype(jnp.bfloat16).astype(x.dtype)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, state)

    with _restored_through(bf16):
        yield


@contextlib.contextmanager
def restored_element_altered():
    """The restore hands back its state with one element of the first
    floating leaf changed."""
    def alter(state):
        leaves, tree = jax.tree.flatten(state)
        for i, x in enumerate(leaves):
            if jnp.issubdtype(x.dtype, jnp.floating):
                flat = np.array(x).reshape(-1)
                flat[flat.size // 2] += 1.0
                leaves[i] = jnp.asarray(flat.reshape(x.shape))
                break
        return jax.tree.unflatten(tree, leaves)

    with _restored_through(alter):
        yield


@contextlib.contextmanager
def fp8_control():
    """The training cells' control: the plain reference computed in fp8
    (``refcheck.fp8``) with its own AdamW, jitted in the place of the
    program's step and driven on the program's state by the loop."""
    from harness import program, refcheck

    seen = {}
    real_config = program.model_config

    def model_config(cell, ref):
        seen.update(config=cell.config, ref=ref)
        return real_config(cell, ref)

    def train_step(mc, traffic, shardings=None):
        ref, c, h = seen["ref"], seen["config"], traffic["optimizer"]
        capture = traffic["capture"] == "fused"

        def step(state, batch):
            opt, t = state["opt"], state["opt"]["step"] + 1
            with jax.default_matmul_precision("highest"):
                loss, grads = jax.value_and_grad(ref.loss)(
                    state["params"], batch["tokens"], c, refcheck.fp8)
                params, m, v, _ = refcheck.adamw(
                    state["params"], opt["m"], opt["v"], grads,
                    t.astype(jnp.float32), h)
            new = {"params": params, "opt": {"m": m, "v": v, "step": t}}
            if not capture:
                return new, {"loss": loss}
            snap = jax.lax.optimization_barrier(
                jax.tree.map(lambda x: x + jnp.zeros((), x.dtype), new))
            return new, snap, {"loss": loss}

        return program.jit_step(step, capture, shardings)

    with mock.patch.object(program, "model_config", model_config), \
            mock.patch.object(program, "train_step", train_step):
        yield


@contextlib.contextmanager
def exchange_left_out():
    """On a mesh, each chip takes the loss and gradient of its own rows
    and none is exchanged: the reduction over the data axis is left out,
    and each chip updates the shard of the state it holds with its own
    gradient (the loss read is one chip's)."""
    from jax.sharding import PartitionSpec as P

    from repro import runtime
    from repro.train import optimizer, steps

    def make(cfg, *, lr=3e-4, capture=False):
        loss_fn = steps.make_loss_fn(cfg)

        def local_grad(params, tokens):
            with runtime.use_mesh(None):   # inside, every axis is manual
                return jax.value_and_grad(loss_fn)(params,
                                                   {"tokens": tokens})

        def train_step(state, batch):
            mesh = runtime.get_mesh()
            local = jax.shard_map(
                local_grad, mesh=mesh, in_specs=(P(), P(runtime.data_axes(mesh))),
                out_specs=P(), check_vma=False)
            loss, grads = local(state["params"], batch["tokens"])
            params, opt, metrics = optimizer.adamw_update(
                grads, state["opt"], state["params"], lr=lr)
            metrics["loss"] = loss
            new = {"params": params, "opt": opt}
            if not capture:
                return new, metrics
            snap = jax.lax.optimization_barrier(
                jax.tree.map(lambda x: x + jnp.zeros((), x.dtype), new))
            return new, snap, metrics
        return train_step

    with mock.patch.object(steps, "make_train_step", make):
        yield


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "exchange_left_out": exchange_left_out,
          "stored_byte_flipped": stored_byte_flipped,
          "restored_bf16": restored_bf16,
          "restored_element_altered": restored_element_altered,
          "fp8_control": fp8_control}
