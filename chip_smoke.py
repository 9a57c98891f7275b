"""Smoke test of the checkpoint/restart main path on a TPU.

    python chip_smoke.py            # one chip: device, kernels, train, erasure
    python chip_smoke.py --chips 4  # four chips: the sharded phase only

Everything runs in this one process (a chip belongs to one process).  Each
phase prints one line of what it checked, with its wall time and the time
spent compiling; any failed check raises, so the exit code is non-zero.

  device   platform, device kind and count; a backend that is not a TPU
           fails here, before anything runs.
  kernels  every Pallas kernel of repro.kernels, compiled (a Mosaic custom
           call in the compiled program, never interpreted) on device arrays
           at real widths, against the repro.kernels.ref oracles run on the
           host CPU.
  train    repro.launch.train.main at full veloc-demo-100m width: fused
           capture, async device-delta checkpoints every 10 steps, a
           simulated kill after the second one and the in-process restore.
           Then a fresh Cluster over the same directory restores the newest
           version, which must equal the final train state byte for byte.
  erasure  4 simulated ranks with XOR parity (group 4) save 128 MiB each,
           then a device-delta version in which ~1% of the chunks changed
           (fingerprint-diff and gather kernels on the save path); one rank's
           node tiers are dropped and its restore of the newest version is
           rebuilt from parity, byte-identical.
  sharded  (--chips 4) veloc-demo-100m with FSDP on a data=4 mesh: a sync
           checkpoint restored under the same shardings, byte for byte and
           spread over all four devices, and the device-side L2 partner and
           XOR encodes against their host oracles.

The compile cache lives where JAX_COMPILATION_CACHE_DIR says, else in
<checkout>/.jax_cache.  The last line of stdout is one JSON object:
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
"""
import argparse
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SCRATCH = ROOT / ".smoke_scratch"
ARCH = "veloc-demo-100m"
#: per-rank state of the erasure phase: f32 and bf16 leaves, 64 MiB each
ERASURE_SHAPES = {"w": ((4096, 4096), "float32"),
                  "m": ((4096, 8192), "bfloat16")}

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileMeter:
    """Backend compile seconds and persistent-cache hits/misses, read from
    JAX's monitoring events; ``take()`` returns and resets the counts."""

    def __init__(self, jax):
        self._reset()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _reset(self):
        self.compile_s, self.hits, self.misses = 0.0, 0, 0

    def _duration(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            self.compile_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def take(self) -> str:
        out = (f"compile_s={self.compile_s:.3f} cache_hits={self.hits} "
               f"cache_misses={self.misses}")
        self._reset()
        return out


def _same_bytes(a_tree, b_tree) -> int:
    """Assert two pytrees hold the same leaves byte for byte; returns the
    byte count compared."""
    import jax
    import numpy as np

    a_leaves, b_leaves = jax.tree.leaves(a_tree), jax.tree.leaves(b_tree)
    if jax.tree.structure(a_tree) != jax.tree.structure(b_tree):
        raise AssertionError("restored tree structure differs")
    total = 0
    for i, (a, b) in enumerate(zip(a_leaves, b_leaves)):
        a, b = np.asarray(a), np.asarray(b)
        if a.dtype != b.dtype or a.shape != b.shape \
                or a.tobytes() != b.tobytes():
            raise AssertionError(
                f"leaf {i}: {b.dtype}{b.shape} differs from the saved "
                f"{a.dtype}{a.shape}")
        total += a.nbytes
    return total


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_kernels(seed: int) -> str:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import checksum as ck
    from repro.kernels import ops, ref
    from repro.kernels import quantize as qz
    from repro.kernels import xor_parity as xp

    interpret = ops.interpret_mode()
    if interpret:
        raise AssertionError("kernels would run interpreted")
    cpu = jax.devices("cpu")[0]
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))

    def words(shape):
        return jax.random.bits(next(keys), shape, jnp.uint32)

    def host_ref(fn, *args):
        out = fn(*(jax.device_put(np.asarray(a), cpu) for a in args))
        return jax.tree.map(np.asarray, out)

    def run(name, fn, *args):
        compiled = jax.jit(fn).lower(*args).compile()
        if "tpu_custom_call" not in compiled.as_text():
            raise AssertionError(f"{name}: no Mosaic kernel was compiled")
        return jax.tree.map(np.asarray, compiled(*args))

    def equal(name, got, want):
        if not np.array_equal(got, want):
            raise AssertionError(f"{name}: differs from the ref oracle")

    checked = []
    x = words((512, ck.CHUNK_WORDS))
    equal("checksum", run("checksum", lambda a: ck.checksum_pallas(
        a, interpret=False), x), host_ref(ref.checksum_ref, x))
    checked.append("checksum(512x2048)")

    chunk = 64 * 1024 // 4  # 64 KiB delta chunks
    x = words((64, chunk))
    equal("blockhash", run("blockhash", lambda a: ck.blockhash_pallas(
        a, interpret=False), x), host_ref(ref.blockhash_ref, x))
    prev = jnp.asarray(host_ref(ref.blockhash_ref, x))
    dirty_rows = [3, 40]
    x2 = x.at[dirty_rows, 5].set(x[dirty_rows, 5] ^ jnp.uint32(1))
    fp, dirty = run("blockhash_diff", lambda a, p: ck.blockhash_diff_pallas(
        a, p, interpret=False), x2, prev)
    equal("blockhash_diff fingerprints", fp, host_ref(ref.blockhash_ref, x2))
    if np.nonzero(dirty[:, 0])[0].tolist() != dirty_rows:
        raise AssertionError(f"blockhash_diff flags {np.nonzero(dirty)[0]}")
    idx = jnp.asarray([40, 3, 63, 0, 3, 17, 17, 9], jnp.int32)
    equal("gather_rows", run("gather_rows", lambda a, i: ck.gather_rows_pallas(
        a, i, interpret=False), x2, idx), np.asarray(x2)[np.asarray(idx)])
    checked.append(f"blockhash/diff/gather(64x{chunk})")

    n = 1 << 20
    for k in (4, 16):
        x = words((k, n))
        equal(f"xor_reduce K={k}", run(f"xor_reduce K={k}",
              lambda a: xp.xor_reduce_pallas(a, interpret=False), x),
              host_ref(ref.xor_reduce_ref, x))
    a, b = words((n,)), words((n,))
    equal("xor_pair", run("xor_pair", lambda u, v: xp.xor_pair_pallas(
        u, v, interpret=False), a, b), host_ref(ref.xor_pair_ref, a, b))
    checked.append(f"xor_reduce(K=4,16 x {n}) xor_pair")

    x = jax.random.normal(next(keys), (4096, qz.BLOCK_SIZE), jnp.float32) * 3
    q, s = run("quantize", lambda a: qz.quantize_pallas(
        a, interpret=False), x)
    qr, sr = host_ref(ref.quantize_ref, x)
    off = np.abs(q.astype(np.int32) - qr.astype(np.int32))
    # the device may round a quotient that sits on a .5 tie the other way
    if off.max() > 1 or (off > 0).mean() >= 1e-3:
        raise AssertionError(f"quantize: {int((off > 0).sum())} codes off")
    np.testing.assert_allclose(s, sr, rtol=1e-6)
    back = run("dequantize", lambda u, v: qz.dequantize_pallas(
        u, v, interpret=False), jnp.asarray(qr), jnp.asarray(sr))
    np.testing.assert_allclose(back, host_ref(ref.dequantize_ref, qr, sr),
                               rtol=1e-6)
    checked.append(f"quantize/dequantize(4096x{qz.BLOCK_SIZE})")
    return "compiled and equal to ref: " + ", ".join(checked)


def phase_train(seed: int) -> str:
    from repro.core import Cluster, TierTopology, VelocClient
    from repro.kernels import ops
    from repro.launch import train

    scratch = SCRATCH / "train"
    shutil.rmtree(scratch, ignore_errors=True)
    before = dict(ops.KERNEL_DISPATCHES)
    run = train.main([
        "--arch", ARCH, "--steps", "30", "--ckpt-every", "10",
        "--fail-at", "25", "--mode", "async", "--capture", "fused",
        "--delta", "--device-delta", "--seed", str(seed),
        "--scratch", str(scratch)])
    used = {k: v - before[k] for k, v in ops.KERNEL_DISPATCHES.items()}
    if run.restored_from != 20 or run.version != 30:
        raise AssertionError(
            f"kill at step 25 restored v{run.restored_from} (want v20); "
            f"newest saved v{run.version} (want v30)")
    # what a restarted process sees: a new cluster over the same directory
    client = VelocClient(run.pipeline,
                         Cluster(TierTopology(scratch=str(scratch))))
    try:
        v, restored = client.restart_latest(run.state)
    finally:
        client.shutdown()
    if v != run.version:
        raise AssertionError(f"fresh restart_latest found v{v}, want "
                             f"v{run.version}: {client.restart_diagnostics}")
    nbytes = _same_bytes(run.state, restored)
    return (f"{ARCH} 30 steps, kill at 25 restored v{run.restored_from}, "
            f"fresh cluster restored v{v}: {nbytes} bytes identical; "
            f"loss {run.losses[0]:.4f}->{run.losses[-1]:.4f}; "
            f"kernel dispatches {used}")


def phase_erasure(seed: int) -> str:
    import jax
    import jax.numpy as jnp

    from repro.core import (Cluster, ModuleSpec, PipelineSpec, TierTopology,
                            VelocClient)
    from repro.kernels import ops

    group = 4
    scratch = SCRATCH / "erasure"
    shutil.rmtree(scratch, ignore_errors=True)
    spec = PipelineSpec(name="smoke-xor", mode="sync", device_delta=True,
                        modules=[ModuleSpec("delta"), ModuleSpec("serialize"),
                                 ModuleSpec("local"),
                                 ModuleSpec("xor", {"group_size": group})])
    cluster = Cluster(TierTopology(scratch=str(scratch)), nranks=group,
                      group_size=group)
    clients = [VelocClient(spec, cluster, rank=r) for r in range(group)]

    def touch(x):
        """Change one word in every 100th 64 KiB chunk."""
        step = 100 * 64 * 1024 // x.dtype.itemsize
        return x.reshape(-1).at[::step].add(1).reshape(x.shape)

    try:
        keys = jax.random.split(jax.random.PRNGKey(seed), group)
        states = [{name: jax.random.normal(
                       jax.random.fold_in(keys[r], i), shape, jnp.float32
                   ).astype(dtype)
                   for i, (name, (shape, dtype))
                   in enumerate(sorted(ERASURE_SHAPES.items()))}
                  for r in range(group)]
        for client, state in zip(clients, states):
            client.checkpoint(state, version=1).result()
        # version 2 is a delta: the device diff flags the touched chunks
        # and the gather kernel packs only them for the host
        gathers = ops.KERNEL_DISPATCHES["gather"]
        states = [jax.tree.map(touch, state) for state in states]
        for client, state in zip(clients, states):
            kind = client.checkpoint(state, version=2).result()["delta_kind"]
            if kind != "delta":
                raise AssertionError(f"version 2 saved as {kind!r}")
        gathers = ops.KERNEL_DISPATCHES["gather"] - gathers
        if gathers < group:
            raise AssertionError(f"{gathers} gathers for {group} delta saves")
        lost = 1
        cluster.fail_node(lost)
        xor_before = ops.KERNEL_DISPATCHES["xor"]
        v, restored = clients[lost].restart_latest(states[lost])
        if v != 2:
            raise AssertionError(f"rank {lost} restore found v{v}: "
                                 f"{clients[lost].restart_diagnostics}")
        if ops.KERNEL_DISPATCHES["xor"] == xor_before:
            raise AssertionError("restore did not rebuild from XOR parity")
        nbytes = _same_bytes(states[lost], restored)
    finally:
        for client in clients:
            client.shutdown()
    return (f"{group} ranks x {nbytes} bytes, XOR group {group}; v1 full, "
            f"v2 delta ({gathers} device gathers); rank {lost}'s node tiers "
            f"dropped, v2 rebuilt from parity through the chain, "
            f"byte-identical")


def phase_sharded(seed: int) -> str:
    import jax
    import numpy as np

    from jax.sharding import NamedSharding, PartitionSpec

    from repro import runtime
    from repro.configs.base import ShapeCfg, get_config
    from repro.core import Cluster, PipelineSpec, TierTopology, VelocClient
    from repro.core.partner import (encode_l2, flatten_local_u32,
                                    ring_xor_parity_ref)
    from repro.launch.mesh import make_host_mesh
    from repro.train.data import SyntheticStream
    from repro.train.steps import (init_train_state, make_train_step,
                                   resolve_state_shardings)

    mesh = make_host_mesh(data=4)
    devices = list(mesh.devices.reshape(-1))
    cfg = get_config(ARCH).replace(fsdp=True)
    key = jax.random.PRNGKey(seed)
    steps = 3
    with runtime.use_mesh(mesh):
        init = lambda k: init_train_state(k, cfg)  # noqa: E731
        sh = resolve_state_shardings(cfg, mesh, jax.eval_shape(init, key))
        state = jax.jit(init, out_shardings=sh)(key)
        step_fn = jax.jit(make_train_step(cfg), donate_argnums=(0,),
                          out_shardings=(sh, NamedSharding(mesh,
                                                           PartitionSpec())))
        stream = SyntheticStream(cfg, ShapeCfg("smoke", 256, 8, "train"),
                                 mesh=mesh)
        for i in range(steps):
            state, metrics = step_fn(state, stream.batch(i))
        loss = float(metrics["loss"])
    if not np.isfinite(loss):
        raise AssertionError(f"sharded loss {loss}")

    def spread(tree, what):
        for leaf in jax.tree.leaves(tree):
            if len(leaf.sharding.device_set) != len(devices):
                raise AssertionError(f"{what}: a {leaf.shape} leaf lives on "
                                     f"{leaf.sharding.device_set}")

    spread(state, "train state")
    n_sharded = sum(not leaf.sharding.is_fully_replicated
                    for leaf in jax.tree.leaves(state))

    scratch = SCRATCH / "sharded"
    shutil.rmtree(scratch, ignore_errors=True)
    spec = PipelineSpec(name="smoke-fsdp", mode="sync")
    client = VelocClient(spec, Cluster(TierTopology(scratch=str(scratch))))
    try:
        client.checkpoint(state, version=steps).result()
    finally:
        client.shutdown()
    client = VelocClient(spec, Cluster(TierTopology(scratch=str(scratch))))
    try:
        v, restored = client.restart_latest(state, shardings=sh)
    finally:
        client.shutdown()
    if v != steps:
        raise AssertionError(f"sharded restore found v{v}: "
                             f"{client.restart_diagnostics}")
    nbytes = _same_bytes(state, restored)
    spread(restored, "restored state")
    for a, b in zip(jax.tree.leaves(sh), jax.tree.leaves(restored)):
        if not b.sharding.is_equivalent_to(a, b.ndim):
            raise AssertionError(f"restored {b.shape} under {b.sharding}, "
                                 f"saved under {a}")

    # device-side L2 encodes vs host oracles over each device's local bytes
    cpu = jax.devices("cpu")[0]

    def local_buffer(dev):
        parts = [next(s.data for s in leaf.addressable_shards
                      if s.device == dev)
                 for leaf in jax.tree.leaves(state)]
        buf = np.asarray(flatten_local_u32(
            [jax.device_put(np.asarray(p), cpu) for p in parts]))
        return np.pad(buf, (0, (-buf.shape[0]) % 1024))

    def slices(out):
        by_dev = {s.device: np.asarray(s.data) for s in out.addressable_shards}
        return [by_dev[d] for d in devices]

    bufs = [local_buffer(d) for d in devices]
    pspecs = jax.tree.map(lambda s: s.spec, sh)
    out = encode_l2(state, pspecs, mesh, mode="partner")
    spread(out, "partner copy")
    for g, got in enumerate(slices(out)):
        if not np.array_equal(got, bufs[(g - 1) % len(devices)]):
            raise AssertionError(f"partner copy on device {g} differs")
    out = encode_l2(state, pspecs, mesh, mode="xor")
    spread(out, "xor parity")
    for g, (got, want) in enumerate(zip(slices(out),
                                        ring_xor_parity_ref(bufs))):
        if not np.array_equal(got, want):
            raise AssertionError(f"xor parity stripe {g} differs")
    return (f"{ARCH} fsdp data=4, {steps} steps loss {loss:.4f}; "
            f"{n_sharded} sharded leaves, restore {nbytes} bytes identical "
            f"on all {len(devices)} devices under the saved shardings; "
            f"L2 partner + xor equal to host oracles")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded four-chip phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from repro import runtime

    cache = runtime.use_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    meter = CompileMeter(jax)
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} compile_cache={cache}", flush=True)
    phases = [("sharded", phase_sharded)] if args.chips == 4 else [
        ("kernels", phase_kernels), ("train", phase_train),
        ("erasure", phase_erasure)]
    t_all = time.perf_counter()
    for name, phase in phases:
        t0 = time.perf_counter()
        line = phase(args.seed)
        print(f"{name}: {line} | wall_s={time.perf_counter() - t0:.3f} "
              f"{meter.take()}", flush=True)
    shutil.rmtree(SCRATCH, ignore_errors=True)
    print(f"total: wall_s={time.perf_counter() - t_all:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
