"""The plain forward pass of Arcee's ``afmoe`` (Trinity-Mini), for one
chip's share of the experts.

float32 throughout, every product at ``highest`` precision, no cache, no
kernels, no batching: causal attention over the whole sequence, a block of
query rows at a time so that a 17k-token replay's scores fit; weights stay
bfloat16 and are widened a matrix at a time. Follows the published
``config.json`` (``model_type`` ``afmoe``) and, for what no key says,
``modeling_afmoe.py`` as the issue's writer knows it — each such point is
listed under ``assumed`` in the configuration file.

With ``x`` the residual stream, every norm RMSNorm (eps ``rms_norm_eps``)::

    x = E[ids] * sqrt(hidden_size)                       (mup_enabled)
    layer l:  h = N1(x);  q = Nq(h Wq), k = Nk(h Wk) per head;  v = h Wv
              sliding layer: q, k rotated (whole head); full layer: NOT
              query i sees key j iff j <= i and, sliding, i - j < sliding_window
              a = softmax(q k / sqrt(head_dim)) v;  a = a * sigmoid(h Wg)
              x = x + N2(a Wo);  u = N3(x);  x = x + N4(F_l(u))
    F_l = SwiGLU(intermediate_size) for l < num_dense_layers, MoE after
    MoE(u) = sum_{chosen, held j} w_j SwiGLU_j(u) + SwiGLU_shared(u)
    s = sigmoid(u Wr); chosen = the num_experts_per_tok largest of s + b;
    w_j = route_scale * s_j / (sum_chosen s + 1e-20)
    logits = Nf(x) W_head

The share: experts ``first_expert .. first_expert + n_experts_held - 1``
are held; the router keeps every output and every pick; ``MoE`` is the held
experts' part plus the shared expert, and what the absent experts would add
is left out — as in the program.

**Positions not comparable** (``TOLERANCE``): ``blocks/joyai``'s rule, this
block's own copy. A chosen expert weighs about ``2.826 / 8`` of one expert
FFN, and the cut between the 8th and the 9th of 128 sigmoid scores is
dense: where a HELD expert sits within ``TOLERANCE`` of the cut in any of
the expert layers (:func:`cut_margin`), bfloat16 rounding of the router's
input swaps it in or out, the next layers' inputs move, and the served token
differs from the reference's for no fault of the program. ``logits``
declares such a position not comparable; the gap is read over the rest.

``lowp`` is the control of ``correct``. ``kv_fp8`` rounds what the cache
holds (keys after their norm and rotation, values) to float8_e4m3;
``act_fp8`` rounds every ACTIVATION the configuration states as bfloat16 —
the input of every product, queries, keys and values going into attention,
the residual stream after each sublayer; ``fp8`` does both. **``no_window``**
is the same float32 pass with EVERY layer attending to every earlier
position: what the program would compute if the window were ignored, or if
rows the manager had given back were still read.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

Q_BLOCK = 256
SLIDING = "sliding_attention"
# Of ``s + b`` (sigmoid scores, 0 to 1). blocks/joyai's and
# blocks/nemotron_h's 0.005 leaves 1-3% of this model's positions to compare:
# 30 expert layers, 16 held experts each, and the narrowest of them decides.
# On the chip's own replays (every position's gap kept beside its margin:
# the configuration's limits file) the sound gaps over 0.1 all sit under a
# margin of 0.002, and at 0.003 8-11% of the positions compare.
TOLERANCE = 0.003


def _f32(x):
    return x.astype(jnp.float32)


def _fp8(x):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _act(x, lowp):
    """An activation the program keeps in the activations' dtype, as the
    control keeps it: rounded to float8_e4m3."""
    return _fp8(x) if lowp in ("act_fp8", "fp8") else x


def _mm(x, w, lowp):
    return _act(x, lowp) @ _f32(w)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def rope(x, positions, theta):
    """x [T, H, hd]: rotate (first half, second half) pairs, the whole head."""
    hd = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(q, k, v, window):
    """Causal softmax attention, q [T, H, hd], k, v [T, KV, hd], query head
    ``r`` reading KV head ``r // (H / KV)``, in blocks of Q_BLOCK query rows
    (T a multiple of Q_BLOCK). ``window``: a query at ``i`` sees ``j`` iff
    ``i - j < window`` too (None: every earlier position)."""
    t, h, hd = q.shape
    kv = k.shape[1]
    qb = (q * (1.0 / jnp.sqrt(jnp.float32(hd)))).reshape(t // Q_BLOCK, Q_BLOCK, kv, h // kv, hd)
    cols = jnp.arange(t)

    def block(args):
        i, qi = args
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.einsum("bkgd,skd->kgbs", qi, k)
        seen = cols[None, :] <= rows[:, None]
        if window is not None:
            seen &= rows[:, None] - cols[None, :] < window
        s = jnp.where(seen[None, None], s, -1e30)
        return jnp.einsum("kgbs,skd->bkgd", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(block, (jnp.arange(t // Q_BLOCK), qb))
    return out.reshape(t, h * hd)


@partial(jax.jit, static_argnames=("n_heads", "n_kv", "hd", "theta", "eps", "rotate",
                                   "window", "lowp"))
def gated_attention(h, wq, wk, wv, wg, wo, q_norm, k_norm, positions, *, n_heads,
                    n_kv, hd, theta, eps, rotate, window, lowp):
    """One attention block over the whole sequence, h [T, D] (already
    normed): (a * sigmoid(h Wg)) Wo."""
    t = h.shape[0]
    q = rms_norm(_mm(h, wq, lowp).reshape(t, n_heads, hd), q_norm, eps)
    k = rms_norm(_mm(h, wk, lowp).reshape(t, n_kv, hd), k_norm, eps)
    v = _mm(h, wv, lowp).reshape(t, n_kv, hd)
    if rotate:
        q, k = rope(q, positions, theta), rope(k, positions, theta)
    if lowp in ("kv_fp8", "fp8"):  # what the cache holds
        k, v = _fp8(k), _fp8(v)
    a = attention(_act(q, lowp), _act(k, lowp), _act(v, lowp), window)
    return _mm(a * jax.nn.sigmoid(_mm(h, wg, lowp)), wo, lowp)


@partial(jax.jit, static_argnames=("lowp",))
def ffn(u, w_gate, w_up, w_down, lowp):
    return _mm(jax.nn.silu(_mm(u, w_gate, lowp)) * _mm(u, w_up, lowp), w_down, lowp)


@partial(jax.jit, static_argnames=("top_k", "scale"))
def route(u, router, bias, *, top_k, scale):
    """Weights of every expert [T, experts]: ``scale * s / (sum of the
    chosen s + 1e-20)`` where chosen, 0 where not; ``bias`` moves the choice
    only."""
    s = jax.nn.sigmoid(u @ router)
    _, chosen = jax.lax.top_k(s + bias, top_k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    rows = jnp.arange(u.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(
        scale * picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20))


@partial(jax.jit, static_argnames=("top_k", "first", "held"))
def cut_margin(u, router, bias, *, top_k, first, held):
    """[T]: the smallest distance in ``s + b`` by which a HELD expert is
    inside or outside the chosen ``top_k`` — what rounding has to move a
    score by before this share's part of ``MoE(u)`` changes its experts."""
    v = jax.nn.sigmoid(u @ router) + bias
    top, chosen = jax.lax.top_k(v, top_k + 1)
    lowest_in, highest_out = top[:, top_k - 1], top[:, top_k]
    is_in = jnp.zeros(v.shape, bool).at[jnp.arange(v.shape[0])[:, None], chosen[:, :top_k]].set(True)
    is_held = (jnp.arange(v.shape[1]) >= first) & (jnp.arange(v.shape[1]) < first + held)
    held_in = jnp.min(jnp.where(is_in & is_held, v, jnp.inf), axis=-1)
    held_out = jnp.max(jnp.where(~is_in & is_held, v, -jnp.inf), axis=-1)
    return jnp.minimum(held_in - highest_out, lowest_in - held_out)


def moe(u, lp, e, cfg, lowp):
    """``MoE(u)`` of this share in expert layer ``e``, [T, D]."""
    first, held = cfg["first_expert"], cfg["n_experts_held"]
    w = route(u, lp["router"][e], lp["router_bias"][e], top_k=cfg["num_experts_per_tok"],
              scale=float(cfg["route_scale"]))
    m = ffn(u, lp["s_gate"][e], lp["s_up"][e], lp["s_down"][e], lowp)  # the shared expert
    for j in range(held):  # one expert's matrices widened at a time
        m = m + w[:, first + j, None] * ffn(u, lp["e_gate"][e, j], lp["e_up"][e, j],
                                            lp["e_down"][e, j], lowp)
    return m


def layer(x, lp, l, cfg, positions, lowp):
    """Layer ``l``. Returns (x', the router's :func:`cut_margin` [T], or
    None for a dense layer)."""
    eps, k = float(cfg["rms_norm_eps"]), cfg["num_dense_layers"]
    sliding = cfg["layer_types"][l] == SLIDING
    window = cfg["sliding_window"] if sliding and lowp != "no_window" else None
    h = rms_norm(x, lp["norm1"][l], eps)
    a = gated_attention(
        h, lp["wq"][l], lp["wk"][l], lp["wv"][l], lp["wg"][l], lp["wo"][l],
        lp["q_norm"][l], lp["k_norm"][l], positions, n_heads=cfg["num_attention_heads"],
        n_kv=cfg["num_key_value_heads"], hd=cfg["head_dim"], theta=float(cfg["rope_theta"]),
        eps=eps, rotate=sliding, window=window, lowp=lowp)
    x = _act(x + rms_norm(a, lp["norm2"][l], eps), lowp)  # the stream is an activation too
    u = rms_norm(x, lp["norm3"][l], eps)
    if l < k:
        f, margin = ffn(u, lp["d_gate"][l], lp["d_up"][l], lp["d_down"][l], lowp), None
    else:
        e = l - k
        margin = cut_margin(u, lp["router"][e], lp["router_bias"][e],
                            top_k=cfg["num_experts_per_tok"], first=cfg["first_expert"],
                            held=cfg["n_experts_held"])
        f = moe(u, lp, e, cfg, lowp)
    return _act(x + rms_norm(f, lp["norm4"][l], eps), lowp), margin


@partial(jax.jit, static_argnames=("eps",))
def head(h_rows, final_norm, lm_head, *, eps):
    return rms_norm(h_rows, final_norm, eps) @ _f32(lm_head)


def _padded(ids):
    t = len(ids)
    t_pad = -(-t // Q_BLOCK) * Q_BLOCK  # causal: the padding sees, is not seen
    return jnp.asarray(list(ids) + [0] * (t_pad - t), jnp.int32), jnp.arange(t_pad, dtype=jnp.int32)


def trunk(params, cfg, tokens, positions, lowp=None):
    """(The last hidden state before the final norm [T_pad, D], the smallest
    :func:`cut_margin` over the expert layers [T_pad])."""
    x = _f32(params["embed"][tokens])
    if cfg["mup_enabled"]:
        x = x * jnp.sqrt(jnp.float32(cfg["hidden_size"]))
    margin = jnp.full(tokens.shape, jnp.inf)
    for l in range(cfg["num_hidden_layers"]):
        x, m = layer(x, params["layers"], l, cfg, positions, lowp)
        margin = margin if m is None else jnp.minimum(margin, m)
    return x, margin


def logits_and_margins(params: dict, cfg: dict, ids: list[int], n_last: int,
                       lowp: str | None = None):
    """(float32 logits [n_last, vocab] of the LAST ``n_last`` positions of
    ``ids``, by a full forward pass over all of it; the smallest cut margin
    of each of those positions [n_last])."""
    tokens, positions = _padded(ids)
    first = len(ids) - n_last
    with jax.default_matmul_precision("highest"):
        x, margin = trunk(params, cfg, tokens, positions, lowp)
        rows = -(-n_last // 128) * 128  # few head programs, whatever n_last
        h_rows = jnp.pad(x[first:first + n_last], ((0, rows - n_last), (0, 0)))
        lg = head(h_rows, params["final_norm"], params["lm_head"],
                  eps=float(cfg["rms_norm_eps"]))[:n_last]
    return lg, margin[first:first + n_last]


def logits(params: dict, cfg: dict, ids: list[int], n_last: int, lowp: str | None = None):
    """(logits [n_last, vocab]; not comparable [n_last]: a held expert
    within ``TOLERANCE`` of the router's cut in some expert layer at that
    position)."""
    lg, margin = logits_and_margins(params, cfg, ids, n_last, lowp)
    return lg, margin < TOLERANCE
