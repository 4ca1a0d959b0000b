"""The plain forward pass of the LongCat-Flash double layer, for one chip's
share of the experts.

float32 throughout, every product at ``highest`` precision, no cache, no
kernels, no batching: full causal attention over the whole sequence in the
EXPANDED form (per-head keys and values made from the latent — the program
runs the absorbed form), a block of query rows at a time so the scores fit;
weights stay bfloat16 and are widened a matrix at a time (the 5.17 B
parameters held here do not fit in float32). Follows the published
``config.json`` and the family's description; what the config does not
settle is listed under ``assumed`` in the configuration file (no
renormalisation of the chosen weights, half-split rope pairs, the untied
head).

One published layer, ``h`` the residual stream::

    for i in (0, 1):
        a = h + MLA_i(RMSNorm(h)); u = RMSNorm(a)
        if i == 0: m = MoE(u)
        h = a + FFN_i(u)
    h = h + m

The share: experts ``first_expert .. first_expert + n_experts_held - 1``
are held; the router keeps every output and every pick; ``MoE`` is the held
experts' part plus the identity experts' part, and what the absent experts
would add is left out — as in the program.

**Every served position is compared.** The experts a token runs are a
discrete choice, the ``moe_topk`` largest of ``s + b``, and bfloat16
rounding upstream of the router can swap the last chosen with the first
not chosen in the program. At this size that moves nothing ``correct``
can see: a chosen expert weighs ``6 s_j``, about 0.03, beside the residual
stream and two dense FFNs, and on the chip the gap's mean (0.10) and its
largest value were the same with the near-ties in or out, at every
tolerance from 0 to 8% of the margin (PERF.md section 2). So the block
declares no position not comparable, and ``not_comparable_share`` keeps
the default limit of 0.

``lowp`` is the control of ``correct``: ``kv_fp8`` rounds what the cache
holds (the latent and the rotated key) to float8_e4m3, ``act_fp8`` rounds
every matmul's activation input, ``fp8`` does both.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

Q_BLOCK = 256


def _f32(x):
    return x.astype(jnp.float32)


def _fp8(x):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _mm(x, w, lowp):
    if lowp in ("act_fp8", "fp8"):
        x = _fp8(x)
    return x @ _f32(w)


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


def attention(q, k, v):
    """Causal softmax attention, q/k [T, H, qk], v [T, H, hv], in blocks of
    Q_BLOCK query rows (T is a multiple of Q_BLOCK)."""
    t, h, qk = q.shape
    qb = (q * (1.0 / jnp.sqrt(jnp.float32(qk)))).reshape(t // Q_BLOCK, Q_BLOCK, h, qk)
    cols = jnp.arange(t)

    def block(args):
        i, qi = args
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.einsum("bhd,shd->hbs", qi, k)
        s = jnp.where(cols[None, None, :] <= rows[None, :, None], s, -1e30)
        return jnp.einsum("hbs,shd->bhd", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(block, (jnp.arange(t // Q_BLOCK), qb))
    return out.reshape(t, h * v.shape[-1])


@partial(jax.jit, static_argnames=("n_heads", "nope", "rope_dim", "v_dim", "theta", "eps", "lowp"))
def mla(x, wq_a, q_norm, wq_b, wkv_a, kv_norm, wkv_b, wo, positions, *,
        n_heads, nope, rope_dim, v_dim, theta, eps, lowp):
    """One MLA block over the whole sequence, x [T, D] (already normed)."""
    t, d = x.shape
    q_rank, rank = wq_a.shape[1], wkv_b.shape[0]
    cq = rms_norm(_mm(x, wq_a, lowp), q_norm, eps) * jnp.sqrt(jnp.float32(d / q_rank))
    q = _mm(cq, wq_b, lowp).reshape(t, n_heads, nope + rope_dim)
    ckr = _mm(x, wkv_a, lowp)
    c = rms_norm(ckr[:, :rank], kv_norm, eps) * jnp.sqrt(jnp.float32(d / rank))
    kr = rope(ckr[:, None, rank:], positions, theta)  # ONE for all heads
    if lowp in ("kv_fp8", "fp8"):  # what the cache holds
        c, kr = _fp8(c), _fp8(kr)
    kv = _mm(c, wkv_b, lowp).reshape(t, n_heads, nope + v_dim)
    qf = jnp.concatenate([q[..., :nope], rope(q[..., nope:], positions, theta)], axis=-1)
    kf = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(kr, (t, n_heads, rope_dim))], axis=-1)
    return _mm(attention(qf, kf, kv[..., nope:]), wo, lowp)


@partial(jax.jit, static_argnames=("lowp",))
def ffn(u, w_gate, w_up, w_down, lowp):
    return _mm(jax.nn.silu(_mm(u, w_gate, lowp)) * _mm(u, w_up, lowp), w_down, lowp)


@partial(jax.jit, static_argnames=("top_k", "scale"))
def route(u, router, bias, *, top_k, scale):
    """Weights of every output [T, outputs]: ``scale * s`` where chosen, 0
    where not; ``bias`` moves the choice only."""
    s = jax.nn.softmax(u @ router, axis=-1)
    _, chosen = jax.lax.top_k(s + bias, top_k)
    rows = jnp.arange(u.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(
        scale * jnp.take_along_axis(s, chosen, axis=-1))


def moe(u, lp, li, cfg, lowp):
    """``MoE(u)`` of this share, [T, D]."""
    first, held, n_routed = cfg["first_expert"], cfg["n_experts_held"], cfg["n_routed_experts"]
    w = route(u, lp["router"][li], lp["router_bias"][li], top_k=cfg["moe_topk"],
              scale=float(cfg["routed_scaling_factor"]))
    m = jnp.sum(w[:, n_routed:], axis=-1, keepdims=True) * u  # identity experts
    for e in range(held):  # one expert's matrices widened at a time
        m = m + w[:, first + e, None] * ffn(u, lp["e_gate"][li, e], lp["e_up"][li, e],
                                            lp["e_down"][li, e], lowp)
    return m


@partial(jax.jit, static_argnames=("eps",))
def head(h_rows, final_norm, lm_head, *, eps):
    return rms_norm(h_rows, final_norm, eps) @ _f32(lm_head)


def logits(params: dict, cfg: dict, ids: list[int], n_last: int, lowp: str | None = None):
    """float32 logits [n_last, vocab] of the LAST ``n_last`` positions of
    ``ids``, by a full forward pass over all of it."""
    t = len(ids)
    t_pad = -(-t // Q_BLOCK) * Q_BLOCK  # causal: the padding sees, is not seen
    tokens = jnp.asarray(list(ids) + [0] * (t_pad - t), jnp.int32)
    positions = jnp.arange(t_pad, dtype=jnp.int32)
    eps, lp = float(cfg["rms_norm_eps"]), params["layers"]
    statics = dict(n_heads=cfg["num_attention_heads"], nope=cfg["qk_nope_head_dim"],
                   rope_dim=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
                   theta=float(cfg["rope_theta"]), eps=eps, lowp=lowp)
    with jax.default_matmul_precision("highest"):
        h = _f32(params["embed"][tokens])
        for li in range(cfg["num_layers"]):
            m = None
            for i in (0, 1):
                x = rms_norm(h, lp["in_norm"][li, i], eps)
                a = h + mla(x, lp["wq_a"][li, i], lp["q_norm"][li, i], lp["wq_b"][li, i],
                            lp["wkv_a"][li, i], lp["kv_norm"][li, i], lp["wkv_b"][li, i],
                            lp["wo"][li, i], positions, **statics)
                u = rms_norm(a, lp["post_norm"][li, i], eps)
                if i == 0:  # the shortcut
                    m = moe(u, lp, li, cfg, lowp)
                h = a + ffn(u, lp["w_gate"][li, i], lp["w_up"][li, i], lp["w_down"][li, i], lowp)
            h = h + m
        rows = -(-n_last // 128) * 128  # few head programs, whatever n_last
        h_rows = jnp.pad(h[t - n_last:t], ((0, rows - n_last), (0, 0)))
        out = head(h_rows, params["final_norm"], params["lm_head"], eps=eps)[:n_last]
    return out
