"""The system under test, reached through the calls the trainer
(``repro.launch.train``) makes: the model configuration, the jitted
training step, the checkpoint pipeline and its client.  Module attributes
are looked up at call time, so a test can plant a fault in the program."""
from __future__ import annotations

import dataclasses

import jax

from harness.spec import Cell


def model_config(cell: Cell, ref):
    """The program's configuration as the file states it, checked against
    the file's widths by the reference."""
    from repro.configs import base

    prog = cell.config["program"]
    mc = base.get_config(prog["arch"]).replace(**prog.get("fields", {}))
    ref.check_program(cell.config, mc)
    return mc


def check_state(state, mc) -> None:
    """The benchmark's state has the tree, shapes and dtypes of the
    program's own ``init_train_state``."""
    from repro.train import steps

    want = jax.eval_shape(lambda: steps.init_train_state(
        jax.random.PRNGKey(0), mc))
    got = jax.eval_shape(lambda: state)
    if jax.tree.structure(want) != jax.tree.structure(got):
        raise ValueError("state tree differs from the program's")
    for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        if (w.shape, w.dtype) != (g.shape, g.dtype):
            raise ValueError(f"state leaf {g.shape} {g.dtype} differs from "
                             f"the program's {w.shape} {w.dtype}")


def train_step(mc, traffic: dict):
    """``jax.jit(make_train_step(cfg, capture=...), donate_argnums=(0,))``
    as the trainer builds it."""
    from repro.train import steps

    capture = traffic["capture"] == "fused"
    fn = steps.make_train_step(mc, lr=traffic["optimizer"]["lr"],
                               capture=capture)
    return jax.jit(fn, donate_argnums=(0,))


def pipeline_spec(traffic: dict):
    """The ``PipelineSpec`` the traffic file states field by field."""
    from repro.core import ModuleSpec, PipelineSpec

    fields = dict(traffic["pipeline"])
    names = {f.name for f in dataclasses.fields(PipelineSpec)}
    if set(fields) != names:
        raise ValueError(
            f"traffic pipeline must state every PipelineSpec field: "
            f"missing {sorted(names - set(fields))}, "
            f"unknown {sorted(set(fields) - names)}")
    fields["modules"] = [ModuleSpec(m["name"], dict(m.get("options", {})),
                                    m.get("priority"))
                         for m in fields["modules"]]
    return PipelineSpec(**fields)


def client(pipeline, scratch: str):
    """A client on the default tier topology (DRAM and node-local file
    tiers, the ``pfs`` file tier) rooted at ``scratch``."""
    from repro.core import Cluster, TierTopology, VelocClient

    return VelocClient(pipeline, Cluster(TierTopology(scratch=scratch)))


def shard_key(name: str, version: int, rank: int = 0) -> str:
    from repro.core import format as fmt

    return fmt.shard_key(name, version, rank)


def warm_save_path(snap) -> int:
    """The save's host work without the tier writes: copy the snapshot to
    the host, serialize it and digest every region and the shard, so every
    digest shape is compiled before the window.  Returns the shard's
    bytes."""
    from repro.core import capture, format as fmt
    from repro.kernels import ops as kops

    regions = list(capture.iter_host_regions(snap))
    shard = fmt.serialize_shard(regions, {"step": 0, "loss": 0.0})
    kops.digest(shard)
    return len(shard)
