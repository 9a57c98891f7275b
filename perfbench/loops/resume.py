"""Time to resume after a process restart on the same node.

Set-up trains ``setup_steps`` steps with the trainer's calls, saves
the state through the traffic's pipeline and waits until the save is
durable at every level, then runs the next step to learn its loss without
the interruption.  The window repeats recoveries until ``--seconds`` have
passed.  A recovery builds a new ``Cluster`` and ``VelocClient`` on the
scratch, as a restarted process would (so the in-process DRAM tier is
empty), calls ``restart_latest`` and runs one step to its loss on the host.
Each restored state is compared with the saved snapshot byte for byte (the
comparison's own time is left out of the recovery's) and each loss with the
uninterrupted one, exactly.

Traffic keys: ``seq_len``, ``batch``, ``capture``, ``optimizer`` (``lr``),
``setup_steps`` and ``pipeline`` (every ``PipelineSpec`` field).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import program, rows, spec, stats, tracing, weights
from harness.context import Context, Outcome, memory_peak_bytes

SETTLE_S = 240.0


def _bits(x):
    """The array's bits as unsigned integers of its width."""
    width = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]
    return jax.lax.bitcast_convert_type(x, width)


@jax.jit
def leaves_differing(a, b):
    """How many leaves of ``a`` differ from ``b`` in any bit."""
    return sum(jnp.any(_bits(x) != _bits(y)).astype(jnp.int32)
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def run(ctx: Context) -> Outcome:
    cell, tr = ctx.cell, ctx.cell.traffic
    if tr.get("layout") is not None:
        raise ValueError("the resume loop runs on one device; it takes no "
                         "layout")
    ref = spec.reference(cell)
    mc = program.model_config(cell, ref)
    layout = ref.layout(cell.config)
    key = weights.seed_key(ctx.seed)
    shape = (tr["batch"], tr["seq_len"], mc.vocab_size)
    pipe = program.pipeline_spec(tr)
    k = tr["setup_steps"]
    capture = tr["capture"] == "fused"

    def feed(step):
        return {"tokens": jnp.asarray(rows.tokens(ctx.seed, step, *shape))}

    # -- set-up: train, save, wait until durable, the uninterrupted step ---
    step_fn = program.train_step(mc, tr)
    state = weights.state_maker(layout)(key)
    program.check_state(state, mc)
    client = program.client(pipe, str(ctx.scratch))
    try:
        for s in range(k):
            client.tick("step_begin")
            out = step_fn(state, feed(s))
            client.tick("step_end")
            state, snap = out[0], (out[1] if capture else None)
            loss = float(out[-1]["loss"])
        fut = client.checkpoint(state, version=k, snap=snap,
                                meta={"step": k, "loss": loss})
        res = fut.result(SETTLE_S)
        if fut.module_errors or fut.skipped:
            raise RuntimeError(f"set-up save failed: {res}")
        saved = snap if snap is not None else jax.tree.map(jnp.copy, state)
    finally:
        client.shutdown()
    out = step_fn(state, feed(k))
    loss_after = float(out[-1]["loss"])
    del state, out
    template = jax.eval_shape(lambda: saved)
    int(leaves_differing(saved, saved))   # compiled before the window
    shard_bytes = res.get("shard_bytes")

    # -- the window: recoveries until the time is up -----------------------
    recs = []
    if ctx.trace:
        tracing.start(str(ctx.trace_dir))
    compiles0 = ctx.compiles.total()
    setup_s = ctx.setup_s()
    ctx.log(f"setup {setup_s:.3f} s; window opens")
    with tracing.span(tracing.WINDOW_SPAN):
        t_open = time.perf_counter()
        deadline = t_open + ctx.seconds
        while time.perf_counter() < deadline:
            recs.append(_recover(ctx, pipe, template, saved, step_fn, feed,
                                 k))
        t_close = time.perf_counter()
    window_compiles = ctx.compiles.total() - compiles0
    summary = None
    if ctx.trace:
        tracing.stop()
        path = tracing.newest_xplane(str(ctx.trace_dir))
        summary = tracing.reduce(tracing.load_events(path)) if path else None
    mem = memory_peak_bytes()
    region_bytes = [int(np.prod(x.shape)) * x.dtype.itemsize
                    for x in jax.tree.leaves(template)]
    del saved

    failed = sum(1 for r in recs if r["version"] != k)
    differing = sum(r["differing"] for r in recs)
    gap = max((abs(r["loss"] - loss_after) for r in recs), default=np.inf)
    checks = {"restored_leaves_differing": (differing, 0),
              "resume_loss_gap": (gap, cell.limits["resume_loss_gap"]),
              "recoveries_wrong_version": (failed, 0)}
    e2e = {"setup_s": setup_s}
    if recs and not failed:
        e2e["resume_s"] = stats.mean([r["resume_s"] for r in recs])
    ctx.log(f"window {t_close - t_open:.3f} s, recoveries {recs}, "
            f"uninterrupted loss {loss_after!r}, compiles in window "
            f"{window_compiles}")
    record = {"window_s": t_close - t_open, "recoveries": recs,
              "region_bytes": region_bytes, "shard_bytes": shard_bytes,
              "state_bytes": sum(region_bytes)}
    return Outcome(e2e=e2e, checks=checks, attempted=len(recs),
                   failed=failed, record=record, memory_peak_bytes=mem,
                   window_compiles=window_compiles, trace=summary)


def _recover(ctx, pipe, template, saved, step_fn, feed, k) -> dict:
    """One recovery, timed from the new client to the step's loss."""
    t0 = time.perf_counter()
    with tracing.span("bench.recovery"):
        client = program.client(pipe, str(ctx.scratch))
        try:
            with tracing.span("bench.restore"):
                r0 = time.perf_counter()
                version, restored = client.restart_latest(template)
                restore_s = time.perf_counter() - r0
            if restored is None:
                return {"version": None, "differing": len(
                    jax.tree.leaves(template)), "loss": np.inf,
                    "resume_s": np.nan, "restore_call_s": restore_s}
            with tracing.span("bench.compare"):
                c0 = time.perf_counter()
                differing = int(leaves_differing(restored, saved))
                compare_s = time.perf_counter() - c0
            client.tick("step_begin")
            out = step_fn(restored, feed(k))
            client.tick("step_end")
            loss = float(out[-1]["loss"])
            t1 = time.perf_counter()
            del out, restored
        finally:
            client.shutdown()
    return {"version": version, "differing": differing, "loss": loss,
            "resume_s": t1 - t0 - compare_s, "restore_call_s": restore_s}
