"""The plain forward pass of Nemotron-H for one chip's share of the experts.

float32 throughout, every product at ``highest`` precision, no cache, no
state pool, no kernels, no batching, no chunking: the Mamba-2 rule runs
TOKEN BY TOKEN from a zero state over the whole sequence (the program runs
it in blocks of ``chunk_size`` and carries the state between calls), the
convolution over the whole sequence from zero padding, full causal
attention over the whole sequence a block of query rows at a time; weights
stay bfloat16 and are widened a matrix at a time. Follows the published
``config.json`` (``model_type`` ``nemotron_h``); each departure is listed
under ``assumed`` in the configuration file.

Layer ``i``, its kind the ``i``-th letter of ``hybrid_override_pattern``,
every norm a plain RMSNorm::

    h = h + mixer_i(norm(h))

    M: [z | xBC | dt] = u W_in;  xBC = silu(conv(xBC) + b);  [x | B | C] = xBC
       dt = softplus(dt + dt_bias);  A = -exp(A_log)
       S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t;  y_t = S_t C_t + D x_t
       (per head, head h reading group h // (heads / n_groups))
       out = norm_g(y * silu(z)) W_out     (the norm over each group apart)
    *: q, k, v = u Wq, u Wk, u Wv (no bias, no norm, NO rotary embedding)
       out = softmax(q k^T / sqrt(head_dim), causal) v Wo
    E: s = sigmoid(u W_r); chosen = the num_experts_per_tok largest of s + b
       w_j = routed_scaling_factor * s_j / sum_chosen s
       out = sum_{chosen, held j} w_j W_down,j relu(W_up,j u)^2 + W_down relu(W_up u)^2

The share: experts ``first_expert .. first_expert + n_experts_held - 1`` are
held; the router keeps every output and every pick; an expert layer is the
held experts' part plus the shared expert, and what the absent experts
would add is left out — as in the program.

**Positions not comparable** (``TOLERANCE``; ``blocks/joyai/forward.py``
has the rule's reasons at length). A chosen expert weighs about ``2.5 / 6``
of one expert of width 1856, and the cut between the 6th and the 7th of 128
sigmoid scores is dense: where a HELD expert sits within ``TOLERANCE`` of
the cut in any of the 23 expert layers (:func:`cut_margin`), bfloat16
rounding of the router's input swaps it in or out, the swap moves the next
layers' inputs, and further swaps follow: the served token then differs
from the reference's for no fault of the program. ``logits`` declares such a
position not comparable; :func:`logits_and_margins` hands back the margins
themselves, so that a tolerance can be judged on a run's own numbers. On the
chip over EVERY position the widest gap reads 0.95-1.95 as stated and
2.28-3.78 with the reference in float8: too near to set a limit between
(the configuration's limits file has every reading). Unlike joyai's, a
position far from every cut is not clean here: a swap at an EARLIER token
stays in the Mamba layers' state and in the attention layers' keys, so 1.3%
of the positions that compare still read over 0.5 (none of 552 over 1.0,
where 0.7% of the positions within 0.001 of a cut do), and the limit on the
gap has to stand above that.

``lowp`` is the control of ``correct``: the same pass with what the program
keeps in bfloat16 kept in float8_e4m3 instead. ``kv_fp8`` rounds what the
paged cache holds (keys and values); ``act_fp8`` rounds every ACTIVATION the
configuration states as bfloat16 — the input of every product, queries
going into attention, and the residual stream after each layer (norms, the
router, sigmoid, softmax and the whole Mamba rule from its projection's
accumulator on stay float32, as stated); ``fp8`` does both.
``state_bf16`` rounds the rule's state to bfloat16 after every token (the
state pool states float32).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

Q_BLOCK = 256
# Distance in ``s + b`` from the router's cut under which a held expert
# makes a position not comparable. The configuration's limits file says
# what it was set from.
TOLERANCE = 0.005


def _f32(x):
    return x.astype(jnp.float32)


def _fp8(x):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _act(x, lowp):
    return _fp8(x) if lowp in ("act_fp8", "fp8") else x


def _mm(x, w, lowp):
    return _act(x, lowp) @ _f32(w)


def norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


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


@partial(jax.jit, static_argnames=("n_heads", "n_kv", "hd", "lowp"))
def attention(x, wq, wk, wv, wo, *, n_heads, n_kv, hd, lowp):
    """x [T, D] (already normed) -> [T, D]. No positions go in."""
    t = x.shape[0]
    q = _act(_mm(x, wq, lowp).reshape(t, n_heads, hd), lowp)
    k = _mm(x, wk, lowp).reshape(t, n_kv, hd)
    v = _mm(x, wv, lowp).reshape(t, n_kv, hd)
    if lowp in ("kv_fp8", "fp8"):  # what the cache holds
        k, v = _fp8(k), _fp8(v)
    k, v = (jnp.repeat(a, n_heads // n_kv, axis=1) for a in (k, v))
    return _mm(causal_attention(q, k, v).reshape(t, n_heads * hd), wo, lowp)


@partial(jax.jit, static_argnames=("heads", "p", "groups", "n", "eps", "lowp"))
def mamba(x, w_in, w_dt, conv, conv_bias, a_log, dt_bias, d_skip, g_norm, w_out, *,
          heads, p, groups, n, eps, lowp):
    """x [T, D] (already normed) -> [T, D]: the recurrence token by token.
    ``W_in``'s columns come as two leaves, ``[z | xBC]`` and ``[dt]``."""
    t = x.shape[0]
    d_inner, width = heads * p, conv.shape[0]
    zxbc, dt = _mm(x, w_in, lowp), _mm(x, w_dt, lowp)
    z, xbc = zxbc[:, :d_inner], zxbc[:, d_inner:]
    padded = jnp.pad(xbc, ((width - 1, 0), (0, 0)))  # causal: zeros before the sequence
    xbc = jax.nn.silu(sum(padded[i:i + t] * _f32(conv[i]) for i in range(width))
                      + _f32(conv_bias))
    xs = xbc[:, :d_inner].reshape(t, heads, p)
    b = xbc[:, d_inner:d_inner + groups * n].reshape(t, groups, n)
    c = xbc[:, d_inner + groups * n:].reshape(t, groups, n)
    b, c = (jnp.repeat(v, heads // groups, axis=1) for v in (b, c))  # head h reads group h // (H / G)
    dt = jax.nn.softplus(dt + dt_bias)
    a = -jnp.exp(a_log)

    def token(s, inputs):
        x_t, b_t, c_t, dt_t = inputs
        s = s * jnp.exp(dt_t * a)[:, None, None] + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        if lowp == "state_bf16":
            s = s.astype(jnp.bfloat16).astype(jnp.float32)
        return s, jnp.einsum("hpn,hn->hp", s, c_t) + d_skip[:, None] * x_t

    _, y = jax.lax.scan(token, jnp.zeros((heads, p, n), jnp.float32), (xs, b, c, dt))
    y = (y.reshape(t, d_inner) * jax.nn.silu(z)).reshape(t, groups, d_inner // groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    return _mm(y.reshape(t, d_inner) * g_norm, w_out, lowp)


@partial(jax.jit, static_argnames=("top_k", "first", "held"))
def cut_margin(u, router, bias, *, top_k, first, held):
    """[T]: the smallest distance in ``s + b`` by which a HELD expert is
    inside or outside the chosen ``top_k`` — what rounding has to move a
    score by before this share's part of the layer changes its experts."""
    v = jax.nn.sigmoid(u @ router) + bias
    top, chosen = jax.lax.top_k(v, top_k + 1)
    lowest_in, highest_out = top[:, top_k - 1], top[:, top_k]
    is_in = jnp.zeros(v.shape, bool).at[jnp.arange(v.shape[0])[:, None], chosen[:, :top_k]].set(True)
    is_held = (jnp.arange(v.shape[1]) >= first) & (jnp.arange(v.shape[1]) < first + held)
    held_in = jnp.min(jnp.where(is_in & is_held, v, jnp.inf), axis=-1)
    held_out = jnp.max(jnp.where(~is_in & is_held, v, -jnp.inf), axis=-1)
    return jnp.minimum(held_in - highest_out, lowest_in - held_out)


@partial(jax.jit, static_argnames=("top_k", "scale", "first", "lowp"))
def moe(u, router, bias, e_up, e_down, s_up, s_down, *, top_k, scale, first, lowp):
    """The expert layer of this share, [T, D]: sigmoid scores, the ``top_k``
    largest of ``s + b``, weights ``scale * s / sum of the chosen s``, the
    held experts' part, and the shared expert (two-matrix ``relu^2`` all)."""
    s = jax.nn.sigmoid(u @ router)
    _, chosen = jax.lax.top_k(s + bias, top_k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    rows = jnp.arange(u.shape[0])[:, None]
    w = jnp.zeros_like(s).at[rows, chosen].set(
        scale * picked / jnp.sum(picked, axis=-1, keepdims=True))

    def ffn(x, wu, wd):
        return _mm(jnp.square(jax.nn.relu(_mm(x, wu, lowp))), wd, lowp)

    def one(acc, xs):  # one held expert's matrices widened at a time
        e, wu, wd = xs
        return acc + jnp.take(w, first + e, axis=1)[:, None] * ffn(u, wu, wd), None

    m, _ = jax.lax.scan(one, jnp.zeros_like(u), (jnp.arange(e_up.shape[0]), e_up, e_down))
    return m + ffn(u, s_up, s_down)


@partial(jax.jit, static_argnames=("eps",))
def head(h_rows, final_norm, lm_head, *, eps):
    return norm(h_rows, final_norm, eps) @ _f32(lm_head)


def logits_and_margins(params: dict, cfg: dict, ids: list[int], n_last: int,
                       lowp: str | None = None):
    """(float32 logits [n_last, vocab] of the LAST ``n_last`` positions of
    ``ids`` by a full forward pass over all of it, and at each of them the
    narrowest :func:`cut_margin` of the expert layers [n_last])."""
    t = len(ids)
    t_pad = -(-t // Q_BLOCK) * Q_BLOCK  # causal: the padding sees, is not seen
    tokens = jnp.asarray(list(ids) + [0] * (t_pad - t), jnp.int32)
    eps, lp = float(cfg["layer_norm_epsilon"]), params["layers"]
    attn_statics = dict(n_heads=cfg["num_attention_heads"], n_kv=cfg["num_key_value_heads"],
                        hd=cfg["head_dim"], lowp=lowp)
    mamba_statics = dict(heads=cfg["mamba_num_heads"], p=cfg["mamba_head_dim"],
                         groups=cfg["n_groups"], n=cfg["ssm_state_size"], eps=eps, lowp=lowp)
    route = dict(top_k=cfg["num_experts_per_tok"], first=cfg["first_expert"])
    at = dict.fromkeys("ME*", 0)
    margin = jnp.full((t_pad,), jnp.inf, jnp.float32)
    with jax.default_matmul_precision("highest"):
        h = _f32(params["embed"][tokens])
        for kind in cfg["hybrid_override_pattern"]:
            i = at[kind]
            at[kind] += 1
            if kind == "M":
                x = norm(h, lp["m_norm"][i], eps)
                o = mamba(x, lp["w_in"][i], lp["w_dt"][i], lp["conv"][i], lp["conv_bias"][i], lp["a_log"][i],
                          lp["dt_bias"][i], lp["d_skip"][i], lp["g_norm"][i], lp["w_out"][i],
                          **mamba_statics)
            elif kind == "*":
                x = norm(h, lp["a_norm"][i], eps)
                o = attention(x, lp["wq"][i], lp["wk"][i], lp["wv"][i], lp["wo"][i],
                              **attn_statics)
            else:
                u = norm(h, lp["e_norm"][i], eps)
                margin = jnp.minimum(margin, cut_margin(
                    u, lp["router"][i], lp["router_bias"][i], held=cfg["n_experts_held"],
                    **route))
                o = moe(u, lp["router"][i], lp["router_bias"][i], lp["e_up"][i], lp["e_down"][i],
                        lp["s_up"][i], lp["s_down"][i],
                        scale=float(cfg["routed_scaling_factor"]), lowp=lowp, **route)
            h = _act(h + o, lowp)  # the residual stream is an activation too
        rows = -(-n_last // 128) * 128  # few head programs, whatever n_last
        h_rows = jnp.pad(h[t - n_last:t], ((0, rows - n_last), (0, 0)))
        out = head(h_rows, params["final_norm"], params["lm_head"], eps=eps)[:n_last]
    return out, margin[t - n_last:t]


def logits(params: dict, cfg: dict, ids: list[int], n_last: int, lowp: str | None = None):
    """(float32 logits [n_last, vocab] of the LAST ``n_last`` positions of
    ``ids``, the positions not comparable [n_last]: a held expert within
    ``TOLERANCE`` of a router's cut in some expert layer)."""
    out, margin = logits_and_margins(params, cfg, ids, n_last, lowp)
    return out, margin < TOLERANCE
