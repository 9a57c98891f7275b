"""Plain reference of a dense decoder-only LM (Phi-3-mini family), written
from the published description and the program's documented state layout,
in float32 at HIGHEST matmul precision, with nothing imported from the
program.

Per layer: RMSNorm (the file's ``rms_norm_eps``) -> multi-head attention
with rotary embeddings on the two halves of each head (the file's
``rope_theta``), causal over every earlier position (the file's
``sliding_window`` must be null), scaled
by 1/sqrt(head_dim) -> residual -> RMSNorm -> SwiGLU MLP -> residual.  Then
a final RMSNorm, an untied output head and the mean next-token
cross-entropy over every position of every row.

Departures from the paper, as the program runs it: the vocabulary's rows
are padded to a multiple of 256 (padding rows are never indexed and their
logits are masked); no dropout, no bias, no LongRoPE scaling (sequences are
within the 4K context).

``quant`` rounds the operands of every matmul; the identity gives the
reference, ``fp8`` the lower-precision control.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from harness.weights import Leaf

HIGHEST = jax.lax.Precision.HIGHEST
VOCAB_PAD = 256
ATTN_CHUNK = 1024

#: the sizes the harness's CPU tests cut the configuration file to (every
#: width cut, every structure kept), and the program configuration's
#: fields that say the same
TINY = {"file": {"hidden_size": 64, "num_attention_heads": 4,
                 "num_key_value_heads": 4, "intermediate_size": 128,
                 "vocab_size": 500, "num_hidden_layers": 2},
        "program": {"d_model": 64, "num_heads": 4, "num_kv_heads": 4,
                    "head_dim": 16, "d_ff": 128, "vocab_size": 500,
                    "num_layers": 2, "remat": False}}


def dims(c: dict) -> dict:
    if c.get("sliding_window") is not None:
        raise ValueError("the reference attends to every earlier position; "
                         "sliding_window must be null")
    d, H = c["hidden_size"], c["num_attention_heads"]
    return {"L": c["num_hidden_layers"], "d": d, "H": H,
            "K": c["num_key_value_heads"], "hd": d // H,
            "f": c["intermediate_size"], "V": c["vocab_size"],
            "Vp": -(-c["vocab_size"] // VOCAB_PAD) * VOCAB_PAD}


def layout(c: dict):
    """Parameter tree in the program's layout: the layers stacked on a
    leading axis inside a one-element group tuple."""
    m = dims(c)
    L, d, H, K, hd, f, Vp = (m[k] for k in ("L", "d", "H", "K", "hd", "f",
                                             "Vp"))
    block = {
        "norm1": Leaf((L, d), "ones"),
        "mix": {"wq": Leaf((L, d, H, hd), fan_in=d),
                "wk": Leaf((L, d, K, hd), fan_in=d),
                "wv": Leaf((L, d, K, hd), fan_in=d),
                "wo": Leaf((L, H, hd, d), fan_in=H * hd)},
        "norm2": Leaf((L, d), "ones"),
        "ffn": {"w_gate": Leaf((L, d, f), fan_in=d),
                "w_up": Leaf((L, d, f), fan_in=d),
                "w_down": Leaf((L, f, d), fan_in=f)},
    }
    return {"emb": Leaf((Vp, d), fan_in=d), "blocks": (block,), "rem": (),
            "final_norm": Leaf((d,), "ones"),
            "lm_head": Leaf((d, Vp), fan_in=d)}


def check_program(c: dict, mc) -> None:
    """The program's configuration holds the file's widths, rotary base
    and attention span."""
    m = dict(dims(c), theta=c["rope_theta"], window=0)
    got = {"L": mc.num_layers, "d": mc.d_model, "H": mc.num_heads,
           "K": mc.num_kv_heads, "hd": mc.head_dim, "f": mc.d_ff,
           "V": mc.vocab_size, "Vp": mc.padded_vocab,
           "theta": mc.rope_theta,
           "window": mc.window if "local_attn" in mc.block_pattern else 0}
    if got != m:
        raise ValueError(f"program config {got} differs from file {m}")


def _mm(spec, a, b, quant):
    return jnp.einsum(spec, quant(a), quant(b), precision=HIGHEST)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def rope(x, T, theta):
    """x: (B, T, H, hd); rotate the first half against the second."""
    half = x.shape[-1] // 2
    inv = theta ** (-(jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv   # (T, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, quant):
    """Causal attention, a block of query rows at a time (recomputed in
    the backward pass) so the score matrix never holds all T x T."""
    B, T, H, hd = q.shape
    c = ATTN_CHUNK if T > ATTN_CHUNK and T % ATTN_CHUNK == 0 else T
    n = T // c
    qc = jnp.moveaxis(q.reshape(B, n, c, H, hd), 1, 0)

    @jax.checkpoint
    def block(args):
        i, qi = args
        s = _mm("bqhd,bkhd->bhqk", qi, k, quant) / math.sqrt(hd)
        qpos = i * c + jnp.arange(c)
        mask = jnp.arange(T)[None, :] <= qpos[:, None]
        s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return _mm("bhqk,bkhd->bqhd", p, v, quant)

    out = jax.lax.map(block, (jnp.arange(n), qc))
    return jnp.moveaxis(out, 0, 1).reshape(B, T, H, hd)


def forward(params, tokens, c: dict, quant=lambda x: x):
    """Logits over the real vocabulary, (B, T, V)."""
    m = dims(c)
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    B, T = tokens.shape
    x = params["emb"][tokens]
    blk = params["blocks"][0]
    for layer in range(m["L"]):
        p = jax.tree.map(lambda a: a[layer], blk)
        h = rms_norm(x, p["norm1"], eps)
        q = rope(_mm("btd,dhk->bthk", h, p["mix"]["wq"], quant), T, theta)
        k = rope(_mm("btd,dhk->bthk", h, p["mix"]["wk"], quant), T, theta)
        v = _mm("btd,dhk->bthk", h, p["mix"]["wv"], quant)
        rep = m["H"] // m["K"]
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        o = attention(q, k, v, quant)
        x = x + _mm("bthk,hkd->btd", o, p["mix"]["wo"], quant)
        h = rms_norm(x, p["norm2"], eps)
        g = _mm("btd,df->btf", h, p["ffn"]["w_gate"], quant)
        u = _mm("btd,df->btf", h, p["ffn"]["w_up"], quant)
        x = x + _mm("btf,fd->btd", jax.nn.silu(g) * u, p["ffn"]["w_down"],
                    quant)
    x = rms_norm(x, params["final_norm"], eps)
    return _mm("btd,dv->btv", x, params["lm_head"][:, :m["V"]], quant)


def loss(params, tokens, c: dict, quant=lambda x: x):
    """Mean cross-entropy of predicting token t+1 from position t."""
    logits = forward(params, tokens, c, quant)[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(nll)
