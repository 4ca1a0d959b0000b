"""The plain forward pass of Qwen3-Next for one chip's share of the experts.

float32 throughout, every product at ``highest`` precision, no cache, no
state pool, no kernels, no batching, no chunking: the gated delta rule runs
TOKEN BY TOKEN from a zero state over the whole sequence (the program runs
it in blocks of 64 and carries the state between calls), the convolution
over the whole sequence from zero padding, full causal attention over the
whole sequence a block of query rows at a time; weights stay bfloat16 and
are widened a matrix at a time. Follows the published ``config.json`` and
the family's description; each departure is listed under ``assumed`` in the
configuration file (the flat ``[q | k | v | z]`` column order, the router in
float32, no next-token-prediction module).

With ``norm1(x; w) = x * rsqrt(mean(x^2) + eps) * (1 + w)``, layer ``i``::

    h = h + mixer_i(norm1(h));  h = h + moe(norm1(h))

the mixer full attention where ``(i + 1) % full_attention_interval == 0``,
the gated delta rule otherwise.

The share: experts ``first_expert .. first_expert + n_experts_held - 1`` are
held; the router keeps every output and every pick and renormalises the
chosen weights over ALL the picks; ``moe`` is the held experts' part plus
the shared expert, and what the absent experts would add is left out — as
in the program.

``lowp`` is the control of ``correct``: ``kv_fp8`` rounds what the paged
cache holds (keys and values) to float8_e4m3, ``act_fp8`` rounds every
matmul's activation input, ``fp8`` does both, ``state_bf16`` rounds the
delta rule's state to bfloat16 after every token (the state pool states
float32).
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


def norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def rope(x, positions, theta):
    """x [T, H, r]: rotate (first half, second half) pairs of all ``r``."""
    r = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def causal_attention(q, k, v):
    """Causal softmax attention over ``sqrt(head size)``, q [T, H, d], k/v
    [T, H, d], a block of Q_BLOCK query rows at a time."""
    t, h, d = q.shape
    qb = (q / jnp.sqrt(jnp.float32(d))).reshape(t // Q_BLOCK, Q_BLOCK, h, d)
    cols = jnp.arange(t)

    def block(args):
        i, qi = args
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.einsum("bhd,shd->hbs", qi, k)
        s = jnp.where(cols[None, None, :] <= rows[None, :, None], s, -1e30)
        return jnp.einsum("hbs,shd->bhd", jax.nn.softmax(s, axis=-1), v)

    return jax.lax.map(block, (jnp.arange(t // Q_BLOCK), qb))


@partial(jax.jit, static_argnames=("n_heads", "n_kv", "hd", "rot", "theta", "eps", "lowp"))
def gated_attention(x, wq, wk, wv, wo, q_norm, k_norm, positions, *, n_heads,
                    n_kv, hd, rot, theta, eps, lowp):
    """x [T, D] (already normed) -> [T, D]."""
    t = x.shape[0]
    qg = _mm(x, wq, lowp).reshape(t, n_heads, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = _mm(x, wk, lowp).reshape(t, n_kv, hd)
    v = _mm(x, wv, lowp).reshape(t, n_kv, hd)
    q, k = norm(q, 1.0 + q_norm, eps), norm(k, 1.0 + k_norm, eps)
    q = jnp.concatenate([rope(q[..., :rot], positions, theta), q[..., rot:]], axis=-1)
    k = jnp.concatenate([rope(k[..., :rot], positions, theta), k[..., rot:]], axis=-1)
    if lowp in ("kv_fp8", "fp8"):  # what the cache holds
        k, v = _fp8(k), _fp8(v)
    k, v = (jnp.repeat(a, n_heads // n_kv, axis=1) for a in (k, v))
    attn = causal_attention(q, k, v).reshape(t, n_heads, hd) * jax.nn.sigmoid(gate)
    return _mm(attn.reshape(t, n_heads * hd), wo, lowp)


@partial(jax.jit, static_argnames=("hk", "hv", "dk", "dv", "eps", "lowp"))
def gated_delta_net(x, w_qkvz, w_ba, conv, a_log, dt_bias, g_norm, w_out, *,
                    hk, hv, dk, dv, eps, lowp):
    """x [T, D] (already normed) -> [T, D]: the recurrence token by token."""
    t = x.shape[0]
    kd, vd, width = hk * dk, hv * dv, conv.shape[0]
    qkvz, ba = _mm(x, w_qkvz, lowp), _mm(x, w_ba, lowp)
    mixed, z = qkvz[:, :2 * kd + vd], qkvz[:, 2 * kd + vd:]
    padded = jnp.pad(mixed, ((width - 1, 0), (0, 0)))  # causal: zeros before the sequence
    mixed = jax.nn.silu(sum(padded[i:i + t] * _f32(conv[i]) for i in range(width)))

    def l2(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)

    q = l2(mixed[:, :kd].reshape(t, hk, dk)) / jnp.sqrt(jnp.float32(dk))
    k = l2(mixed[:, kd:2 * kd].reshape(t, hk, dk))
    q, k = (jnp.repeat(a, hv // hk, axis=1) for a in (q, k))  # a key head serves hv / hk value heads
    v = mixed[:, 2 * kd:].reshape(t, hv, dv)
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(a_log) * jax.nn.softplus(ba[:, hv:] + dt_bias)

    def token(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = s * jnp.exp(g_t)[:, None, None]
        d = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))
        s = s + k_t[:, :, None] * d[:, None, :]
        if lowp == "state_bf16":
            s = s.astype(jnp.bfloat16).astype(jnp.float32)
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((hv, dk, dv), jnp.float32), (q, k, v, g, beta))
    o = norm(o, g_norm, eps) * jax.nn.silu(z.reshape(t, hv, dv))
    return _mm(o.reshape(t, vd), w_out, lowp)


@partial(jax.jit, static_argnames=("top_k", "first", "lowp"))
def moe(u, router, e_gate, e_up, e_down, s_gate, s_up, s_down, s_sig, *,
        top_k, first, lowp):
    """``moe(u)`` of this share, [T, D]: softmax over every expert, the
    ``top_k`` largest renormalised to one, the held experts' part, and the
    shared expert under its sigmoid gate."""
    p = jax.nn.softmax(u @ router, axis=-1)
    top, chosen = jax.lax.top_k(p, top_k)
    rows = jnp.arange(u.shape[0])[:, None]
    w = jnp.zeros_like(p).at[rows, chosen].set(top / jnp.sum(top, axis=-1, keepdims=True))

    def ffn(x, wg, wu, wd):
        return _mm(jax.nn.silu(_mm(x, wg, lowp)) * _mm(x, wu, lowp), wd, lowp)

    def one(acc, xs):  # one held expert's matrices widened at a time
        e, wg, wu, wd = xs
        return acc + jnp.take(w, first + e, axis=1)[:, None] * ffn(u, wg, wu, wd), None

    held = e_gate.shape[0]
    m, _ = jax.lax.scan(one, jnp.zeros_like(u), (jnp.arange(held), e_gate, e_up, e_down))
    return m + jax.nn.sigmoid(u @ _f32(s_sig)) * ffn(u, s_gate, s_up, s_down)


@partial(jax.jit, static_argnames=("eps",))
def head(h_rows, final_norm, lm_head, *, eps):
    return norm(h_rows, 1.0 + final_norm, eps) @ _f32(lm_head)


def logits(params: dict, cfg: dict, ids: list[int], n_last: int, lowp: str | None = None):
    """float32 logits [n_last, vocab] of the LAST ``n_last`` positions of
    ``ids``, by a full forward pass over all of it."""
    t = len(ids)
    t_pad = -(-t // Q_BLOCK) * Q_BLOCK  # causal: the padding sees, is not seen
    tokens = jnp.asarray(list(ids) + [0] * (t_pad - t), jnp.int32)
    positions = jnp.arange(t_pad, dtype=jnp.int32)
    eps, lp, interval = float(cfg["rms_norm_eps"]), params["layers"], cfg["full_attention_interval"]
    hd = cfg["head_dim"]
    attn_statics = dict(n_heads=cfg["num_attention_heads"], n_kv=cfg["num_key_value_heads"],
                        hd=hd, rot=int(hd * cfg["partial_rotary_factor"]),
                        theta=float(cfg["rope_theta"]), eps=eps, lowp=lowp)
    gdn_statics = dict(hk=cfg["linear_num_key_heads"], hv=cfg["linear_num_value_heads"],
                       dk=cfg["linear_key_head_dim"], dv=cfg["linear_value_head_dim"],
                       eps=eps, lowp=lowp)
    with jax.default_matmul_precision("highest"):
        h = _f32(params["embed"][tokens])
        for li in range(cfg["num_hidden_layers"]):
            x = norm(h, 1.0 + lp["in_norm"][li], eps)
            p, j = divmod(li, interval)
            if j == interval - 1:
                h = h + gated_attention(x, lp["wq"][p], lp["wk"][p], lp["wv"][p], lp["wo"][p],
                                        lp["q_norm"][p], lp["k_norm"][p], positions,
                                        **attn_statics)
            else:
                n = p * (interval - 1) + j
                h = h + gated_delta_net(x, lp["w_qkvz"][n], lp["w_ba"][n], lp["conv"][n],
                                        lp["a_log"][n], lp["dt_bias"][n], lp["g_norm"][n],
                                        lp["w_out"][n], **gdn_statics)
            u = norm(h, 1.0 + lp["post_norm"][li], eps)
            h = h + moe(u, lp["router"][li], lp["e_gate"][li], lp["e_up"][li], lp["e_down"][li],
                        lp["s_gate"][li], lp["s_up"][li], lp["s_down"][li], lp["s_sig"][li],
                        top_k=cfg["num_experts_per_tok"], first=cfg["first_expert"], lowp=lowp)
        rows = -(-n_last // 128) * 128  # few head programs, whatever n_last
        h_rows = jnp.pad(h[t - n_last:t], ((0, rows - n_last), (0, 0)))
        out = head(h_rows, params["final_norm"], params["lm_head"], eps=eps)[:n_last]
    return out
