"""The plain forward pass of JoyAI-LLM-Flash, for one chip's share of the
experts, and of its multi-token-prediction module.

float32 throughout, every product at ``highest`` precision, no cache, no
kernels, no batching: full causal attention over the whole sequence in the
EXPANDED form (per-head keys and values made from the latent — the program
runs the absorbed form), a block of query rows at a time so the scores fit;
weights stay bfloat16 and are widened a matrix at a time. Follows the
published ``config.json`` (``model_type`` ``joyai_llm_flash``) and, for the
module, DeepSeek-V3's report, section 2.2; what neither settles is listed
under ``assumed`` in the configuration file.

With ``h`` the residual stream, every norm RMSNorm::

    layer l:  a = h + MLA(RMSNorm(h));  h = a + F_l(RMSNorm(a))
    F_l = SwiGLU(intermediate_size) for l < first_k_dense_replace, MoE after
    MoE(u) = sum_{chosen, held j} w_j SwiGLU_j(u) + SwiGLU_shared(u)
    s = sigmoid(u Wr); chosen = the num_experts_per_tok largest of s + b;
    w_j = routed_scaling_factor * s_j / sum_chosen s

Departures from the published description, each for a stated reason: the
rotary pairs are (first half, second half) where the config says
``rope_interleave`` (a fixed permutation of columns of random weights); the
denominator of ``w_j`` has no ``1e-20`` (a sum of 8 sigmoids is never
near it); no group limit is computed (``n_group`` = ``topk_group`` = 1
makes it the identity).

The share: experts ``first_expert .. first_expert + n_experts_held - 1``
are held; the router keeps every output and every pick; ``MoE`` is the
held experts' part plus the shared expert, and what the absent experts
would add is left out — as in the program.

The module (:func:`draft_logits`): for position ``i``, ``x = Wp
[RMSNorm(Emb(t_{i+1})) ; RMSNorm(h_i)]`` with ``h_i`` the trunk's last
hidden state before its final norm; ``y = Layer(x)``, one MLA + MoE layer
of the same rule over positions ``0 .. i``; ``Head(RMSNorm(y))`` are the
logits of token ``i + 2``. Embedding and head are the trunk's.

**Positions not comparable** (``TOLERANCE``). A chosen expert weighs about
``2.5 / 8`` of one expert FFN of width 768, and the cut between the 8th and
the 9th of 256 sigmoid scores is dense: where a HELD expert sits within
``TOLERANCE`` of the cut in any of the trunk's expert layers — the smallest
distance in ``s + b`` by which a held expert is inside or outside the chosen
eight (:func:`cut_margin`) — bfloat16 rounding of the router's input swaps
it in or out, the swap moves the next layers' inputs, and further swaps
follow: the served token then differs from the reference's for no fault of
the program, by gaps up to 1.0. With no such swap bfloat16 moves a logit by
under 0.05; in float8 the swaps happen at margins ten times wider, at
nearly every position. So ``logits`` declares a position not comparable
where that distance is under ``TOLERANCE`` in any expert layer of the trunk:
the gap is read where the routing is stable under the stated precision, and
there it separates the stated precision from the one below it (readings in
the configuration's limits file). One tolerance serves every layer because
two things cancel: the rounding a router's input carries grows with the
layer (it is the sum of what every earlier sublayer rounded), and what a
swap costs falls with it (a swap in the first expert layer is followed by
six more and reads over 0.3 in one case of five, one in the last by none
and never over 0.2). A swap among absent experts is no such event: it moves
only the denominator of ``w_j``, by the tie's margin.

``lowp`` is the control of ``correct``: the same pass with what the program
keeps in bfloat16 kept in float8_e4m3 instead. ``kv_fp8`` rounds what the
cache holds (the latent and the rotated key); ``act_fp8`` rounds every
ACTIVATION the configuration states as bfloat16 — the input of every
product, queries, keys and values going into attention, and the residual
stream after each sublayer (norms, router, sigmoid and softmax stay
float32, as stated); ``fp8`` does both. The residual stream is in it because
this model has nothing that amplifies a rounded product's input (unit-
variance attention scores, no recurrence): with the products' inputs alone
rounded, the widest gap read 0.82-1.43 on the chip beside sound readings of
0.48-1.04 (PERF.md section 2).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

Q_BLOCK = 256
# Of ``s + b`` (sigmoid scores, 0 to 1). bfloat16 moves one score near the
# cut by 0.0008 (first expert layer) to 0.0016 (twelfth), near-normal (a
# third more fits what the chip refused); 0.003 let the chip's served
# positions through at 0.25, 0.28 and 0.31, each with a margin of
# 0.0030-0.0036 in one of the first four expert layers; over 0.004 the
# chip's 26,695 replayed positions hold no gap over 0.05 (readings, and the
# float8 control's, in the configuration's limits file).
TOLERANCE = 0.005


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
    t = x.shape[0]
    rank = wkv_b.shape[0]
    cq = rms_norm(_mm(x, wq_a, lowp), q_norm, eps)
    q = _mm(cq, wq_b, lowp).reshape(t, n_heads, nope + rope_dim)
    ckr = _mm(x, wkv_a, lowp)
    c = rms_norm(ckr[:, :rank], kv_norm, eps)
    kr = rope(ckr[:, None, rank:], positions, theta)  # ONE for all heads
    if lowp in ("kv_fp8", "fp8"):  # what the cache holds
        c, kr = _fp8(c), _fp8(kr)
    kv = _mm(c, wkv_b, lowp).reshape(t, n_heads, nope + v_dim)
    qf = jnp.concatenate([q[..., :nope], rope(q[..., nope:], positions, theta)], axis=-1)
    kf = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(kr, (t, n_heads, rope_dim))], axis=-1)
    # queries, keys and values are the attention products' inputs
    return _mm(attention(_act(qf, lowp), _act(kf, lowp), _act(kv[..., nope:], lowp)), wo, lowp)


@partial(jax.jit, static_argnames=("lowp",))
def ffn(u, w_gate, w_up, w_down, lowp):
    return _mm(jax.nn.silu(_mm(u, w_gate, lowp)) * _mm(u, w_up, lowp), w_down, lowp)


@partial(jax.jit, static_argnames=("top_k", "scale"))
def route(u, router, bias, *, top_k, scale):
    """Weights of every expert [T, experts]: ``scale * s / sum of the
    chosen s`` where chosen, 0 where not; ``bias`` moves the choice only."""
    s = jax.nn.sigmoid(u @ router)
    _, chosen = jax.lax.top_k(s + bias, top_k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    rows = jnp.arange(u.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(
        scale * picked / jnp.sum(picked, axis=-1, keepdims=True))


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
              scale=float(cfg["routed_scaling_factor"]))
    m = ffn(u, lp["s_gate"][e], lp["s_up"][e], lp["s_down"][e], lowp)  # the shared expert
    for j in range(held):  # one expert's matrices widened at a time
        m = m + w[:, first + j, None] * ffn(u, lp["e_gate"][e, j], lp["e_up"][e, j],
                                            lp["e_down"][e, j], lowp)
    return m


def layer(h, lp, block, dense, e, cfg, positions, lowp):
    """Layer with attention block ``block`` and the dense FFN ``dense`` or
    the expert layer ``e`` (the other None). Returns (h', the router's
    :func:`cut_margin` [T], or None for a dense layer)."""
    eps = float(cfg["rms_norm_eps"])
    x = rms_norm(h, lp["in_norm"][block], eps)
    a = h + mla(x, lp["wq_a"][block], lp["q_norm"][block], lp["wq_b"][block],
                lp["wkv_a"][block], lp["kv_norm"][block], lp["wkv_b"][block],
                lp["wo"][block], positions, n_heads=cfg["num_attention_heads"],
                nope=cfg["qk_nope_head_dim"], rope_dim=cfg["qk_rope_head_dim"],
                v_dim=cfg["v_head_dim"], theta=float(cfg["rope_theta"]), eps=eps, lowp=lowp)
    a = _act(a, lowp)  # the residual stream is an activation too
    u = rms_norm(a, lp["post_norm"][block], eps)
    if e is None:
        return _act(a + ffn(u, lp["d_gate"][dense], lp["d_up"][dense], lp["d_down"][dense], lowp), lowp), None
    margin = cut_margin(u, lp["router"][e], lp["router_bias"][e], top_k=cfg["num_experts_per_tok"],
                        first=cfg["first_expert"], held=cfg["n_experts_held"])
    return _act(a + moe(u, lp, e, cfg, lowp), lowp), margin


@partial(jax.jit, static_argnames=("eps",))
def head(h_rows, final_norm, lm_head, *, eps):
    return rms_norm(h_rows, final_norm, eps) @ _f32(lm_head)


def _padded(ids):
    t = len(ids)
    t_pad = -(-t // Q_BLOCK) * Q_BLOCK  # causal: the padding sees, is not seen
    return jnp.asarray(list(ids) + [0] * (t_pad - t), jnp.int32), jnp.arange(t_pad, dtype=jnp.int32)


def trunk(params, cfg, tokens, positions, lowp=None):
    """(The trunk's last hidden state before the final norm [T_pad, D], the
    smallest :func:`cut_margin` over its expert layers [T_pad])."""
    lp, k = params["layers"], cfg["first_k_dense_replace"]
    h = _f32(params["embed"][tokens])
    margin = jnp.full(tokens.shape, jnp.inf)
    for li in range(cfg["num_hidden_layers"]):
        h, m = layer(h, lp, li, li if li < k else None, None if li < k else li - k,
                     cfg, positions, lowp)
        margin = margin if m is None else jnp.minimum(margin, m)
    return h, margin


def _head_rows(h, first, n, norm, lm_head, eps):
    rows = -(-n // 128) * 128  # few head programs, whatever n
    h_rows = jnp.pad(h[first:first + n], ((0, rows - n), (0, 0)))
    return head(h_rows, norm, lm_head, eps=eps)[:n]


def logits(params: dict, cfg: dict, ids: list[int], n_last: int, lowp: str | None = None):
    """(float32 logits [n_last, vocab] of the LAST ``n_last`` positions of
    ``ids``, by a full forward pass of the trunk over all of it; not
    comparable [n_last]: a held expert within ``TOLERANCE`` of the router's
    cut in some expert layer at that position)."""
    tokens, positions = _padded(ids)
    first = len(ids) - n_last
    with jax.default_matmul_precision("highest"):
        h, margin = trunk(params, cfg, tokens, positions, lowp)
        return (_head_rows(h, first, n_last, params["final_norm"], params["lm_head"],
                           float(cfg["rms_norm_eps"])),
                margin[first:first + n_last] < TOLERANCE)


def draft_logits(params: dict, cfg: dict, ids: list[int], n_last: int = 1):
    """The prediction module's float32 logits [n_last, vocab]: row ``j`` is
    made at position ``i = len(ids) - 1 - n_last + j`` from the trunk's
    ``h_i`` and the token ``ids[i + 1]``, and predicts the token at ``i +
    2`` — the last row predicts the token AFTER ``ids``' last."""
    t = len(ids)
    tokens, positions = _padded(ids)
    eps, m = float(cfg["rms_norm_eps"]), params["mtp"]
    L, k = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    with jax.default_matmul_precision("highest"):
        h, _ = trunk(params, cfg, tokens, positions)
        nxt = jnp.roll(tokens, -1)  # position i takes token i + 1
        x = jnp.concatenate([rms_norm(_f32(params["embed"][nxt]), m["e_norm"][0], eps),
                             rms_norm(h, m["h_norm"][0], eps)], axis=-1) @ _f32(m["proj"][0])
        y, _ = layer(x, params["layers"], L, None, L - k, cfg, positions, None)
        return _head_rows(y, t - 1 - n_last, n_last, m["final_norm"][0],
                          params["lm_head"], eps)
