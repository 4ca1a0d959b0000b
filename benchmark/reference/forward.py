"""The plain forward pass of the dense SwiGLU block.

float32 throughout, every matmul at ``highest`` precision, full causal
attention over the whole sequence (computed a block of query rows at a
time so the score matrix fits), one layer's dequantized weights alive at
a time. Follows the published description (Qwen2: q/k/v bias, RoPE on
half-split pairs, RMSNorm, SwiGLU).

``lowp`` is the control of ``correct``: the same pass with one step taken
in the nearest precision below what the configuration states —
``kv_fp8`` rounds keys and values to float8_e4m3 (an fp8 KV cache),
``act_fp8`` rounds every matmul's activation input to float8_e4m3,
``fp8`` does both: everything the configuration holds in bfloat16.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

Q_BLOCK = 512


def _f32(x):
    return x.astype(jnp.float32)


def _deq(w):
    """int8 leaf {"q", "s"} or a dense matrix -> float32 matrix."""
    if isinstance(w, dict):
        return _f32(w["q"]) * w["s"]
    return _f32(w)


def _fp8(x):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _mm(x, w, lowp):
    if lowp in ("act_fp8", "fp8"):
        x = _fp8(x)
    return x @ _deq(w)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def rope(x, positions, theta):
    """x [T, H, hd]: rotate (first half, second half) pairs."""
    hd = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(q, k, v, n_kv):
    """Causal softmax attention, q [T, H, hd], k/v [T, KV, hd], in blocks
    of Q_BLOCK query rows (T is a multiple of Q_BLOCK)."""
    t, h, hd = q.shape
    g = h // n_kv
    qb = (q * (1.0 / jnp.sqrt(jnp.float32(hd)))).reshape(t // Q_BLOCK, Q_BLOCK, n_kv, g, hd)
    cols = jnp.arange(t)

    def block(args):
        i, qi = args
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.einsum("bkgd,skd->kgbs", qi, k)
        s = jnp.where(cols[None, None, None, :] <= rows[None, None, :, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgbs,skd->bkgd", p, v).reshape(Q_BLOCK, h * hd)

    out = jax.lax.map(block, (jnp.arange(t // Q_BLOCK), qb))
    return out.reshape(t, h * hd)


def dense_ffn(y, lp, lowp):
    return _mm(jax.nn.silu(_mm(y, lp["w_gate"], lowp)) * _mm(y, lp["w_up"], lowp),
               lp["w_down"], lowp)


@partial(jax.jit, static_argnames=("n_heads", "n_kv", "theta", "eps", "lowp"))
def layer(h, lp, positions, *, n_heads, n_kv, theta, eps, lowp):
    """One pre-norm transformer layer over the whole sequence h [T, D]."""
    t, d = h.shape
    hd = d // n_heads
    x = rms_norm(h, lp["attn_norm"], eps)
    q, k, v = _mm(x, lp["wq"], lowp), _mm(x, lp["wk"], lowp), _mm(x, lp["wv"], lowp)
    if "bq" in lp:
        q, k, v = q + _f32(lp["bq"]), k + _f32(lp["bk"]), v + _f32(lp["bv"])
    q = rope(q.reshape(t, n_heads, hd), positions, theta)
    k = rope(k.reshape(t, n_kv, hd), positions, theta)
    v = v.reshape(t, n_kv, hd)
    if lowp in ("kv_fp8", "fp8"):
        k, v = _fp8(k), _fp8(v)
    h = h + _mm(attention(q, k, v, n_kv), lp["wo"], lowp)
    return h + dense_ffn(rms_norm(h, lp["mlp_norm"], eps), lp, lowp)


@partial(jax.jit, static_argnames=("eps",))
def head(h_rows, final_norm, lm_head, *, eps):
    return rms_norm(h_rows, final_norm, eps) @ _f32(lm_head)


def logits(params: dict, cfg: dict, ids: list[int], n_last: int,
           lowp: str | None = None):
    """float32 logits [n_last, vocab] of the LAST ``n_last`` positions of
    the sequence ``ids``, by a full forward pass over all of it."""
    t = len(ids)
    t_pad = -(-t // Q_BLOCK) * Q_BLOCK  # causal: the padding sees, is not seen
    tokens = jnp.asarray(list(ids) + [0] * (t_pad - t), jnp.int32)
    positions = jnp.arange(t_pad, dtype=jnp.int32)
    with jax.default_matmul_precision("highest"):
        h = _f32(params["embed"][tokens])
        for i in range(cfg["n_layers"]):
            lp = jax.tree.map(lambda a: a[i], params["layers"])
            h = layer(h, lp, positions, n_heads=cfg["n_heads"],
                      n_kv=cfg["n_kv_heads"], theta=float(cfg["rope_theta"]),
                      eps=float(cfg["norm_eps"]), lowp=lowp)
        w_head = (params["embed"].T if cfg.get("tie_embeddings")
                  else params["lm_head"])
        rows = -(-n_last // 128) * 128  # few head programs, whatever n_last
        h_rows = jnp.pad(h[t - n_last:t], ((0, rows - n_last), (0, 0)))
        return head(h_rows, params["final_norm"], w_head,
                    eps=float(cfg["norm_eps"]))[:n_last]
