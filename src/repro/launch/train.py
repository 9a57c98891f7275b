"""End-to-end resilient training driver with VELOC integrated first-class.

  PYTHONPATH=src python -m repro.launch.train --arch veloc-demo-100m \
      --steps 300 --ckpt-every 20 --mode async --capture fused

Features exercised for real (CPU host):
  - deterministic seekable data stream (restart-exact);
  - DeepFreeze fused L1 capture (snapshot as an output of the jitted step);
  - async multi-level pipeline (local + partner/XOR + external flush);
  - phase-predictor-gated, rate-limited background flushing;
  - automatic restart from the newest restorable level (--resume);
  - simulated node failure (--fail-at N) followed by recovery and replay
    from the restored step;
  - DataStates lineage recording per checkpoint.

A checkpoint that fails, a background pipeline error, or a --resume that
finds nothing restorable (without --cold-start-ok) raises, so the process
exits non-zero instead of training on unprotected.
"""
import argparse
import time
from dataclasses import dataclass
from typing import Any, Optional

import jax

from repro import runtime
from repro.configs.base import ShapeCfg, get_config, smoke_config
from repro.core import (Cluster, DataStates, ModuleSpec, PipelineSpec,
                        TierTopology, VelocClient)
from repro.train.data import SyntheticStream
from repro.train.steps import init_train_state, make_train_step


def build(arch: str, smoke: bool, seq_len: int, batch: int):
    cfg = smoke_config(arch) if smoke else get_config(arch)
    shape = ShapeCfg("cli", seq_len, batch, "train")
    return cfg, shape


#: seconds to wait for in-flight checkpoints to drain (failure simulation
#: and end of run) before the run is declared failed
SETTLE_TIMEOUT_S = 600.0


@dataclass
class TrainRun:
    """What a training run leaves behind: per-step losses, the final train
    state (device arrays), the newest checkpoint version that persisted
    (None when checkpointing was off or nothing was saved), the version the
    failure simulation restored (None when it did not fire), and the
    checkpoint pipeline (what a restarted process rebuilds its client
    from)."""

    losses: list
    state: Any
    version: Optional[int]
    restored_from: Optional[int]
    pipeline: PipelineSpec


def _settle(futures, timeout: float) -> Optional[int]:
    """Wait for every checkpoint future; raise the first failure (a pipeline
    exception, or a level that reported an error).  A version superseded by
    a newer one before it ran is not a failure.  Returns the newest version
    that persisted."""
    newest = None
    for fut in futures:
        if fut.skipped:
            continue
        if fut.exception(timeout) is not None and fut.superseded:
            continue
        fut.result(timeout)
        if fut.module_errors:
            raise RuntimeError(
                f"checkpoint v{fut.version}: levels failed "
                f"{fut.module_errors}: {fut.results.get('errors')}")
        newest = fut.version if newest is None else max(newest, fut.version)
    return newest


def main(argv=None) -> TrainRun:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="veloc-demo-100m")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config of the arch")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--mode", default="async", choices=["async", "sync", "off"])
    ap.add_argument("--capture", default="fused", choices=["fused", "standalone"])
    ap.add_argument("--encoding", default="raw", choices=["raw", "q8", "zlib"])
    ap.add_argument("--delta", action="store_true",
                    help="incremental checkpoints: ship only dirty chunks")
    ap.add_argument("--delta-chunk-kb", type=int, default=64)
    ap.add_argument("--delta-max-chain", type=int, default=8)
    ap.add_argument("--device-delta", action="store_true",
                    help="fingerprint-diff in HBM and gather only dirty "
                         "chunks over PCIe (implies --delta semantics; "
                         "requires --delta)")
    ap.add_argument("--interval-s", type=float, default=None)
    ap.add_argument("--phase-predictor", default="ema",
                    choices=["none", "ema", "gru"])
    ap.add_argument("--scratch", default="/tmp/veloc_train")
    ap.add_argument("--keep-versions", type=int, default=0,
                    help="retain only the newest N checkpoints (0 = all)")
    ap.add_argument("--max-age-s", type=float, default=None,
                    help="retire checkpoints older than this many seconds")
    ap.add_argument("--lane-weight", type=float, default=1.0,
                    help="fair-share weight of this job's backend lane "
                         "when the scratch/backend is shared")
    ap.add_argument("--lane-rate-share", type=float, default=None,
                    help="fraction (0,1] of the cluster flush budget "
                         "this job's lane may use")
    ap.add_argument("--admit-max-queued", type=int, default=None,
                    help="admission high-water mark: over this many "
                         "queued+running checkpoints, new ones skip")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--cold-start-ok", action="store_true",
                    help="with --resume, start from step 0 when nothing is "
                         "restorable instead of failing")
    ap.add_argument("--fail-at", type=int, default=-1,
                    help="simulate node failure after this step")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    runtime.use_compile_cache()
    cfg, shape = build(args.arch, args.smoke, args.seq_len, args.batch)
    key = jax.random.PRNGKey(args.seed)
    stream = SyntheticStream(cfg, shape, seed=1234)

    # single-host run, one rank: local write + external flush, no partner/XOR
    modules = [ModuleSpec("interval", {"interval_s": args.interval_s}),
               ModuleSpec("serialize", {"encoding": args.encoding}),
               ModuleSpec("local"),
               ModuleSpec("flush")]
    if args.delta:
        modules.insert(1, ModuleSpec("delta", {
            "chunk_bytes": args.delta_chunk_kb * 1024,
            "max_chain": args.delta_max_chain}))
    pipeline = PipelineSpec(
        name=f"train-{args.arch}",
        mode="sync" if args.mode == "sync" else "async",
        modules=modules,
        phase_predictor=args.phase_predictor,
        device_delta=args.device_delta,
        keep_versions=args.keep_versions,
        max_age_s=args.max_age_s,
        lane_weight=args.lane_weight,
        lane_rate_share=args.lane_rate_share,
        admit_max_queued=args.admit_max_queued,
    )
    client = None
    if args.mode != "off":
        client = VelocClient(pipeline,
                             Cluster(TierTopology(scratch=args.scratch)))
    try:
        return _train(args, cfg, key, stream, client, pipeline)
    finally:
        if client:
            client.shutdown()


def _train(args, cfg, key, stream, client, pipeline) -> TrainRun:
    ds = DataStates(client.cluster) if client else None
    state = init_train_state(key, cfg)
    step = 0
    if args.resume and client is not None:
        v, restored = client.restart_latest(state)
        if v is not None:
            state, step = restored, v
            print(f"[veloc] resumed from checkpoint v{v}")
        else:
            for d in client.restart_diagnostics:
                print(f"[veloc]   v{d['version']} ({d['level']}) skipped: "
                      f"{d['error']}")
            if not args.cold_start_ok:
                raise RuntimeError(
                    "--resume found no restorable checkpoint under "
                    f"{args.scratch} (pass --cold-start-ok to start fresh)")
            print("[veloc] no checkpoint found; cold start")

    capture = args.capture == "fused" and args.mode != "off"
    step_fn = jax.jit(make_train_step(cfg, lr=args.lr, capture=capture),
                      donate_argnums=(0,))

    losses = []
    futures = []
    restored_from = None
    t_start = time.time()
    while step < args.steps:
        if client:
            client.tick("step_begin")
        batch = stream.batch(step)
        if capture:
            state, snap, metrics = step_fn(state, batch)
        else:
            state, metrics = step_fn(state, batch)
            snap = None
        if client:
            client.tick("step_end")
        step += 1
        loss = float(metrics["loss"])
        losses.append(loss)
        if client and args.ckpt_every and step % args.ckpt_every == 0:
            fut = client.checkpoint(state, version=step, snap=snap,
                                    meta={"step": step, "loss": loss})
            futures.append(fut)
            if ds and not fut.skipped:
                ds.record(step, metrics={"loss": loss})
            blocking_ms = fut.results.get("app_blocking_s", 0) * 1e3
            print(f"step {step}: loss={loss:.4f} "
                  f"ckpt_blocking={blocking_ms:.1f}ms"
                  f"{' (skipped)' if fut.skipped else ''}")
        elif step % 10 == 0:
            print(f"step {step}: loss={loss:.4f}")

        if args.fail_at == step and client and restored_from is None:
            print(f"[failure-sim] killing node state at step {step}; "
                  f"restarting from newest checkpoint")
            _settle(futures, SETTLE_TIMEOUT_S)
            v, restored = client.restart_latest(state)
            if v is None:
                raise RuntimeError(
                    f"failure at step {step}: no restorable checkpoint "
                    f"({client.restart_diagnostics})")
            state, step, restored_from = restored, v, v
            print(f"[failure-sim] recovered at v{v}; replaying from step {v}")

    dt = time.time() - t_start
    print(f"done: {len(losses)} steps in {dt:.1f}s, compiling included"
          + (f"; loss {losses[0]:.4f} -> {losses[-1]:.4f}" if losses else ""))
    version = None
    if client:
        version = _settle(futures, SETTLE_TIMEOUT_S)
        errs = client.backend.errors() if client.backend else []
        if errs:
            raise RuntimeError(f"checkpoint backend errors: {errs[0][:400]}")
    return TrainRun(losses=losses, state=state, version=version,
                    restored_from=restored_from, pipeline=pipeline)


if __name__ == "__main__":
    main()
