"""Device-level L2: partner replication and distributed XOR parity across
the ``data`` mesh axis, as on-device collectives (DESIGN.md §2).

On a real pod these run *before* any host involvement: the snapshot's shards
move across ICI at link bandwidth, so a node loss is survivable even if the
host-side flush never completed.

Both entry points are ONE ``shard_map`` over the full production mesh whose
``in_specs`` are the true parameter PartitionSpecs: inside, each device
flattens its *local* shard blocks into a uint32 buffer (pure local reshape,
zero collectives) and then:

  encode_l2("partner") — collective_permute by +distance along "data": every
      data slot pushes its state bytes to its neighbour (DeepClone-style
      replication without stable storage).  Cost: 1x state bytes on ICI.

  encode_l2("xor")     — SCR/RAID-5 rotating XOR parity via a bandwidth-
      optimal ring reduce-scatter with the Pallas XOR kernel as combiner.
      Faithful SCR layout: each device's buffer is split into G-1 chunks
      assigned to the stripes that do NOT include that device, so the parity
      a device holds never covers its own data; after G-1 permute+XOR steps
      device g holds parity of stripe g.  Any one lost data slot per group
      is reconstructible from survivors + parity (xor_reconstruct_group).
      ICI cost: (G-1)/G x state bytes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.kernels import xor_parity as _xp
from repro.kernels.ops import interpret_mode


def flatten_local_u32(tree):
    """Concatenate a pytree's (local) leaves into one uint32 vector."""
    parts = []
    for leaf in jax.tree.leaves(tree):
        flat = leaf.reshape(-1)
        if flat.dtype in (jnp.float32, jnp.int32):
            parts.append(jax.lax.bitcast_convert_type(flat, jnp.uint32))
        elif flat.dtype == jnp.uint32:
            parts.append(flat)
        elif flat.dtype in (jnp.bfloat16, jnp.float16):
            pad = (-flat.shape[0]) % 2
            if pad:
                flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
            u16 = jax.lax.bitcast_convert_type(flat, jnp.uint16).reshape(-1, 2)
            parts.append(u16[:, 0].astype(jnp.uint32)
                         | (u16[:, 1].astype(jnp.uint32) << 16))
        else:
            parts.append(flat.astype(jnp.uint32))
    return jnp.concatenate(parts)


def _pad_to(x, mult):
    pad = (-x.shape[0]) % mult
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,), x.dtype)])
    return x


def _stripe_layout(buf, g, G):
    """Place the local buffer's G-1 chunks into a (G, c) stripe table with
    row g zeroed (a device's parity stripe never covers its own data)."""
    c = -(-buf.shape[0] // (G - 1))
    buf = _pad_to(buf, c * (G - 1))
    chunks = buf.reshape(G - 1, c)
    j = jnp.arange(G - 1)
    stripes = j + (j >= g)  # skip own stripe index
    return jnp.zeros((G, c), buf.dtype).at[stripes].set(chunks), c


def encode_l2(state, pspecs, mesh, *, mode: str = "xor", axis: str = "data",
              distance: int = 1):
    """state: sharded pytree; pspecs: matching PartitionSpec tree.  Returns a
    1-D uint32 array sharded over the whole mesh — each device's slice is
    the L2 artifact its host must persist (partner copy or parity stripe)."""
    G = mesh.shape[axis]
    assert G >= 2, "L2 encode needs >=2 slots on the partner axis"
    interpret = interpret_mode()
    all_axes = tuple(mesh.axis_names)

    def inner(tree):
        buf = _pad_to(flatten_local_u32(tree), 1024)
        if mode == "partner":
            perm = [(i, (i + distance) % G) for i in range(G)]
            return jax.lax.ppermute(buf, axis, perm)
        # --- SCR rotating-parity ring reduce-scatter -------------------
        g = jax.lax.axis_index(axis)
        xs, c = _stripe_layout(buf, g, G)
        perm = [(i, (i + 1) % G) for i in range(G)]

        def step(i, acc):
            recv = jax.lax.ppermute(acc, axis, perm)
            nxt = jax.lax.dynamic_index_in_dim(xs, (g - 2 - i) % G,
                                               keepdims=False)
            tile = _xp.tile_words(c, _xp.BLOCK_N)
            return _xp.xor_pair_pallas(_pad_to(recv, tile), _pad_to(nxt, tile),
                                       interpret=interpret)[:c]

        init = jax.lax.dynamic_index_in_dim(xs, (g - 1) % G, keepdims=False)
        return jax.lax.fori_loop(0, G - 1, step, init)

    fn = jax.shard_map(inner, mesh=mesh, in_specs=(pspecs,),
                       out_specs=P(all_axes), check_vma=False)
    return fn(state)


# ---------------------------------------------------------------------------
# host-side oracles / recovery (tests + restart path)
# ---------------------------------------------------------------------------


def stripe_table_host(buf: np.ndarray, g: int, G: int) -> np.ndarray:
    c = -(-buf.shape[0] // (G - 1))
    b = np.zeros(c * (G - 1), np.uint32)
    b[: buf.shape[0]] = buf
    chunks = b.reshape(G - 1, c)
    xs = np.zeros((G, c), np.uint32)
    for j in range(G - 1):
        xs[j + (1 if j >= g else 0)] = chunks[j]
    return xs


def ring_xor_parity_ref(buffers: list[np.ndarray]) -> list[np.ndarray]:
    """Oracle: parity stripe each device holds (device g -> stripe g)."""
    G = len(buffers)
    tables = [stripe_table_host(np.asarray(b), g, G) for g, b in enumerate(buffers)]
    out = []
    for s in range(G):
        acc = np.zeros(tables[0].shape[1], np.uint32)
        for g in range(G):
            acc ^= tables[g][s]
        out.append(acc)
    return out


def xor_reconstruct_group(survivor_buffers: dict[int, np.ndarray],
                          parity: dict[int, np.ndarray], lost: int, G: int,
                          length: int) -> np.ndarray:
    """Rebuild the lost device's u32 buffer.  survivor_buffers: {dev: full
    local buffer}; parity: {dev: parity stripe it held}."""
    c = parity[next(d for d in parity if d != lost)].shape[0]
    tables = {d: stripe_table_host(b, d, G) for d, b in survivor_buffers.items()}
    rebuilt = np.zeros((G - 1, c), np.uint32)
    j = 0
    for s in range(G):
        if s == lost:
            continue  # stripe s==lost contains no data from the lost device
        acc = parity[s].copy()  # device s held stripe s parity and s != lost
        for d, t in tables.items():
            acc ^= t[s]
        rebuilt[j] = acc
        j += 1
    return rebuilt.reshape(-1)[:length]
