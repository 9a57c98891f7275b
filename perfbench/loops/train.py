"""Training, with asynchronous checkpoints on a fixed schedule or with none:
the loop of the trainer (``repro.launch.train``) in its order of
calls, with the benchmark's spans and clock around them.

Set-up makes the weights from the seed, builds the step and the client,
runs the first steps through the window's own call and feed (their losses,
first gradient and change are what the reference checks), warms up, and
runs the save path's host work once.  The window then runs steps until
``--seconds`` have passed, saving at the traffic's fixed step positions
counted from the window's first step.  The loop holds no step's
snapshot through the next step.  After it: every save of the window is
awaited, the memory peak is read, the program's state is freed, each save
is read back from each level and compared byte for byte with the state of
its version (made again from the seed through the same compiled step),
and the reference replays the first steps.

Traffic keys: ``seq_len``, ``batch``, ``capture`` ("fused" | "none"),
``optimizer`` (AdamW hyper-parameters; ``lr`` is passed to the step, the
rest are the program's own and the reference uses them), ``check_steps``,
``warmup_steps``, ``saves`` (``first_at`` and ``every``, in window steps;
null for no checkpointing), ``pipeline`` (every ``PipelineSpec`` field;
null for no checkpointing) and, optionally, ``layout`` (the mesh the state
lives on, ``harness.program``; the reference replays the same global
batch on one device).

The record holds ``step_counters``: the window's sum of each scalar the
step returns besides its loss, fetched with the loss, for readers of
counters a configuration's step adds.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from harness import flops, program, refcheck, rows, shardfile, spec, stats
from harness import tracing, weights
from harness.context import Context, Outcome, memory_peak_bytes

#: seconds to wait after the window for its saves to be durable
SETTLE_S = 240.0
#: the step tail is reported only over at least this many steps, so that
#: ten or more lie beyond the 95th percentile
TAIL_MIN_STEPS = 200


def run(ctx: Context) -> Outcome:
    cell, c, tr = ctx.cell, ctx.cell.config, ctx.cell.traffic
    ref = spec.reference(cell)
    mc = program.model_config(cell, ref)
    layout = ref.layout(c)
    key = weights.seed_key(ctx.seed)
    shape = (tr["batch"], tr["seq_len"], mc.vocab_size)
    pipe = program.pipeline_spec(tr) if tr.get("saves") else None
    mesh = program.mesh(tr, cell.chips)
    client = program.client(pipe, str(ctx.scratch)) if pipe else None
    try:
        with program.use_mesh(mesh):
            w = _train(ctx, mc, layout, key, shape, pipe, client, mesh)
    finally:
        if client is not None:
            client.shutdown()

    # the program's state is freed: the reference replays the first steps
    h = tr["optimizer"]
    batches = [rows.tokens(ctx.seed, s, *shape)
               for s in range(tr["check_steps"])]
    got = refcheck.run_reference(ref, c, h, weights.params_maker(layout),
                                 key, batches)
    ctx.log(f"losses: reference {got['losses']} program "
            f"{w['prog']['losses']}")
    g = refcheck.gaps(w["prog"], got)
    checks = {k: (g[k], lim) for k, lim in cell.limits.items()}
    checks["window_losses_nonfinite"] = (w["nonfinite"], 0)
    if mesh is not None:
        checks["state_leaves_off_mesh"] = (w["off_mesh"], 0)
    if pipe is not None:
        checks["ckpt_regions_differing"] = (len(w["bad_regions"]), 0)
        for b in w["bad_regions"][:8]:
            ctx.log(f"read-back differs: {b}")

    B, T = shape[0], shape[1]
    times, saves = w["step_times"], w["saves"]
    e2e = {"setup_s": w["setup_s"],
           "tokens_per_s": stats.rate(len(times) * B * T, w["window_s"])}
    if len(times) >= TAIL_MIN_STEPS:
        e2e["step_p95_ms"] = stats.percentile(times, 95) * 1e3
    if saves and all(np.isfinite(s["protect_s"]) for s in saves):
        e2e["protect_s"] = stats.mean([s["protect_s"] for s in saves])
    ctx.log(f"window {w['window_s']:.3f} s, {len(times)} steps, saves "
            f"{saves}, compiles in window {w['window_compiles']}")
    if times:
        q = [round(stats.percentile(times, p) * 1e3, 3)
             for p in (5, 25, 50, 75, 90, 95, 99, 100)]
        ctx.log(f"step ms at p5/25/50/75/90/95/99/max: {q}")
    active = ref.active_shares(c) if hasattr(ref, "active_shares") else None
    record = {"window_s": w["window_s"], "steps": len(times),
              "tokens_per_s": e2e["tokens_per_s"],
              "flops_per_token": flops.model_flops(weights.shapes(layout), 1,
                                                   active),
              "step_counters": w["counters"],
              "step_times": times, "saves": saves,
              "region_bytes": w["region_bytes"],
              "state_bytes": sum(w["region_bytes"])}
    return Outcome(e2e=e2e, checks=checks,
                   attempted=len(times) + len(saves), failed=w["failed"],
                   record=record, memory_peak_bytes=w["memory_peak_bytes"],
                   window_compiles=w["window_compiles"], trace=w["trace"])


def _train(ctx, mc, layout, key, shape, pipe, client, mesh) -> dict:
    """Set-up, the window and the read-back, on ``mesh`` where there is
    one.  Returns host values only, so the program's device state is freed
    when it returns."""
    tr = ctx.cell.traffic
    capture = tr["capture"] == "fused"
    saves = tr.get("saves")
    b1 = tr["optimizer"]["b1"]
    gstep = 0

    sh = program.state_shardings(mc, mesh, weights.state_maker(layout))
    feed = program.feeder(mesh)
    step_fn = program.train_step(mc, tr, sh)
    make_state = weights.state_maker(layout, sh)
    state = make_state(key)
    program.check_state(state, mc)
    off_mesh = program.off_mesh(state, mesh) if mesh is not None else 0
    fetched = {}

    def one(state):
        """One step as ``_train`` runs it: tick, feed, step, tick, loss
        (and the step's other scalars, in the same transfer)."""
        nonlocal gstep
        if client is not None:
            client.tick("step_begin")
        batch = feed(rows.tokens(ctx.seed, gstep, *shape))
        if capture:
            state, snap, metrics = step_fn(state, batch)
        else:
            (state, metrics), snap = step_fn(state, batch), None
        if client is not None:
            client.tick("step_end")
        gstep += 1
        fetched.update(jax.device_get(metrics))
        return state, snap, float(fetched["loss"])

    # -- the first steps, which the reference follows ----------------------
    state, snap, prog = refcheck.program_readings(
        one, state, tr["check_steps"], b1,
        lambda: weights.params_maker(
            layout, sh["params"] if sh is not None else None)(key))
    for _ in range(tr["warmup_steps"]):
        snap = None
        state, snap, loss = one(state)
    if client is not None:
        program.warm_save_path(snap if snap is not None else state)

    # -- the window --------------------------------------------------------
    step_times, losses, saved, counters = [], [], [], {}
    if ctx.trace:
        tracing.start(str(ctx.trace_dir))
    compiles0 = ctx.compiles.total()
    setup_s = ctx.setup_s()
    ctx.log(f"setup {setup_s:.3f} s; window opens")
    with tracing.span(tracing.WINDOW_SPAN):
        t_open = time.perf_counter()
        deadline = t_open + ctx.seconds
        i = 0
        while time.perf_counter() < deadline:
            t0 = time.perf_counter()
            with tracing.span("bench.step"):
                snap = None
                state, snap, loss = one(state)
                if saves and i >= saves["first_at"] \
                        and (i - saves["first_at"]) % saves["every"] == 0:
                    with tracing.span("bench.save"):
                        saved.append(_save(client, state, snap, gstep, loss,
                                            saved))
            step_times.append(time.perf_counter() - t0)
            losses.append(loss)
            for k, v in fetched.items():
                if k != "loss":
                    counters[k] = counters.get(k, 0.0) + float(v)
            i += 1
        t_close = time.perf_counter()
        for sv in saved:   # the traced window runs on until they are durable
            sv["fut"].wait(SETTLE_S)
    window_compiles = ctx.compiles.total() - compiles0
    summary = None
    if ctx.trace:
        tracing.stop()
        path = tracing.newest_xplane(str(ctx.trace_dir))
        summary = tracing.reduce(tracing.load_events(path)) if path else None
    mem = memory_peak_bytes()
    region_bytes = _region_bytes(state)
    del state, snap

    # -- each save read back from each level, against the same state
    # replayed from the seed through the same compiled step ----------------
    nonfinite = sum(1 for x in losses if not np.isfinite(x))
    failed, save_rows = nonfinite, []
    for sv in saved:
        ok = _settled(sv)
        failed += 0 if ok else 1
        res = sv["fut"].results
        if not ok:
            exc = sv["fut"].exception(0) if sv["fut"].done() else "pending"
            ctx.log(f"save v{sv['version']} did not settle: {exc!r}; "
                    f"queued {sv['queued']}; results {res}")
        save_rows.append({"protect_s": sv.get("t_done", np.nan) - sv["t"],
                          "app_blocking_s": res.get("app_blocking_s"),
                          "shard_bytes": res.get("shard_bytes")})
    bad_regions = _read_back(ctx, client, pipe, saved, step_fn,
                             lambda: make_state(key), feed,
                             shape) if saved else []
    return {"prog": prog, "setup_s": setup_s, "window_s": t_close - t_open,
            "step_times": step_times, "nonfinite": nonfinite,
            "failed": failed, "saves": save_rows, "bad_regions": bad_regions,
            "region_bytes": region_bytes, "memory_peak_bytes": mem,
            "window_compiles": window_compiles, "trace": summary,
            "counters": counters, "off_mesh": off_mesh}


def _region_bytes(state) -> list:
    """Bytes of each region a save of ``state`` writes: one per leaf, or
    one per distinct shard of a leaf split over a mesh."""
    out = []
    for x in jax.tree.leaves(state):
        parts = {}
        for s in x.addressable_shards:
            starts = tuple(i.start or 0 for i in s.index)
            parts.setdefault(starts, s.data.nbytes)
        out += parts.values()
    return out


def _save(client, state, snap, version, loss, saved) -> dict:
    """``client.checkpoint`` as the trainer calls it; the time its
    future settles is noted by a callback on the finishing thread."""
    queued = any(not sv["fut"].done() for sv in saved)
    sv = {"version": version, "queued": queued, "t": time.perf_counter()}
    fut = client.checkpoint(state, version=version, snap=snap,
                            meta={"step": version, "loss": loss})
    sv["fut"] = fut
    fut.add_done_callback(
        lambda f, sv=sv: sv.__setitem__("t_done", time.perf_counter()))
    return sv


def _settled(sv) -> bool:
    """Durable at every level, not skipped, not queued behind another."""
    fut = sv["fut"]
    if sv["queued"] or fut.skipped or not fut.done():
        return False
    return fut.exception(0) is None and not fut.module_errors


def _read_back(ctx, client, pipe, saved, step_fn, make_state, feed,
               shape) -> list:
    """Regions of each save that differ, at each level, from the state of
    its version.  The device held that state only while the save copied
    it, so it is made again: the same weights and rows through the same
    compiled step, which gives the same bits."""
    todo = {sv["version"]: sv for sv in saved}
    state = make_state()
    bad = []
    for s in range(max(todo)):
        out = step_fn(state, feed(rows.tokens(ctx.seed, s, *shape)))
        state = out[0]
        del out
        sv = todo.get(s + 1)
        if sv is None:
            continue
        arrays = [np.asarray(x) for x in jax.tree.leaves(state)]
        key_ = program.shard_key(pipe.name, sv["version"])
        res = sv["fut"].results
        for level, tiers, which in (
                ("L1", client.cluster.node_tiers(0), "l1_tier"),
                ("L3", client.cluster.external_tiers, "l3_tier")):
            tier = next((t for t in tiers if t.info.name == res.get(which)),
                        None)
            blob = tier.get(key_) if tier is not None else None
            if blob is None:
                bad.append(f"v{sv['version']} {level}: missing")
                continue
            bad += [f"v{sv['version']} {level} {n}"
                    for n in shardfile.mismatches(blob, arrays)]
        del arrays
    return bad
