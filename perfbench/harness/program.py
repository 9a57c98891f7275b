"""The system under test, reached through the calls the trainer
(``repro.launch.train``) makes: the model configuration, the jitted
training step, the checkpoint pipeline and its client.  Module attributes
are looked up at call time, so a test can plant a fault in the program.

A traffic file may carry ``layout`` (``{"mesh": {"data": 4, "model": 1},
"fsdp": true}``): the state then lives on that mesh, placed and stepped
as the program's sharded trainer does (``launch.mesh.make_host_mesh``,
``runtime.use_mesh``, ``steps.resolve_state_shardings``), with the batch
split over ``data``.  Without it everything sits on the default device,
as before there were layouts."""
from __future__ import annotations

import contextlib
import dataclasses

import jax
import jax.numpy as jnp

from harness.spec import Cell


def model_config(cell: Cell, ref):
    """The program's configuration as the file states it, checked against
    the file's widths by the reference, with ``fsdp`` from the traffic's
    layout."""
    from repro.configs import base

    prog = cell.config["program"]
    mc = base.get_config(prog["arch"]).replace(**prog.get("fields", {}))
    if cell.traffic.get("layout", {}).get("fsdp"):
        mc = mc.replace(fsdp=True)
    ref.check_program(cell.config, mc)
    return mc


def mesh(traffic: dict, chips: int):
    """The traffic's ``layout`` mesh over the cell's chips, or None."""
    lay = traffic.get("layout")
    if lay is None:
        return None
    from repro.launch.mesh import make_host_mesh

    axes = lay["mesh"]
    if axes.get("data", 1) * axes.get("model", 1) != chips:
        raise ValueError(f"layout mesh {axes} is not the cell's {chips} "
                         f"chips")
    return make_host_mesh(**axes)


def use_mesh(mesh):
    """``runtime.use_mesh`` around a sharded run (the model reads the
    ambient mesh while it is traced); nothing without a mesh."""
    if mesh is None:
        return contextlib.nullcontext()
    from repro import runtime

    return runtime.use_mesh(mesh)


def state_shardings(mc, mesh, make_state):
    """The program's ``NamedSharding`` tree for the state ``make_state``
    (``seed key -> state``) builds, or None without a mesh."""
    if mesh is None:
        return None
    from repro.train import steps

    shapes = jax.eval_shape(make_state, jax.random.PRNGKey(0))
    return steps.resolve_state_shardings(mc, mesh, shapes)


def feeder(mesh):
    """``host tokens -> device batch``: on the default device, or split
    over the mesh's batch axes as the program's ``SyntheticStream``
    places it."""
    if mesh is None:
        return lambda tokens: {"tokens": jnp.asarray(tokens)}
    from jax.sharding import NamedSharding

    from repro.sharding import resolve_spec

    def feed(tokens):
        spec = resolve_spec(tokens.shape, ("batch", None), mesh, False)
        return {"tokens": jax.device_put(tokens, NamedSharding(mesh, spec))}
    return feed


def off_mesh(state, mesh) -> int:
    """How many leaves of ``state`` do not span every device of ``mesh``."""
    want = set(mesh.devices.flat)
    return sum(1 for x in jax.tree.leaves(state)
               if set(x.sharding.device_set) != want)


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


def train_step(mc, traffic: dict, shardings=None):
    """``jax.jit(make_train_step(cfg, capture=...), donate_argnums=(0,))``
    as the trainer builds it; on a mesh with the state's shardings out."""
    from repro.train import steps

    capture = traffic["capture"] == "fused"
    fn = steps.make_train_step(mc, lr=traffic["optimizer"]["lr"],
                               capture=capture)
    return jit_step(fn, capture, shardings)


def jit_step(fn, capture: bool, shardings=None):
    """``fn`` jitted with the state donated; with ``shardings`` the state
    (and the snapshot) come out under them and the metrics replicated, as
    the program's sharded trainer jits its step."""
    if shardings is None:
        return jax.jit(fn, donate_argnums=(0,))
    from jax.sharding import NamedSharding, PartitionSpec

    mesh = jax.tree.leaves(shardings)[0].mesh
    rep = NamedSharding(mesh, PartitionSpec())
    out = (shardings, shardings, rep) if capture else (shardings, rep)
    return jax.jit(fn, donate_argnums=(0,), out_shardings=out)


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
