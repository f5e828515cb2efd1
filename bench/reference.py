"""The plain reference: the served model's forward pass written from its
equations in float32 ``jax.numpy``, evaluated in blocks (one request and
one block of queries at a time, one layer's weights at a time, the output
head in blocks of the vocabulary), so that it fits beside the weights.

It imports nothing of the program and reads only the weights the
benchmark made (``bench/weights.py``) and the configuration file.

Equations (Mistral-family decoder; LLaVA-style image prefix):

  image rows   e = gelu_tanh(m W1) W2, placed before the prompt's rows
  embedding    x = E[token]
  norm         rms(x, w) = x / sqrt(mean(x^2) + eps) * (1 + w)
  attention    q = rope(h Wq), k = rope(h Wk), v = h Wv over 8 KV heads
               shared by groups of query heads; rope rotates the two
               halves of each head by pos * theta^(-2i/Dh); causal softmax
               of q k / sqrt(Dh)
  block        x += attn(rms(x, n1)) Wo;  x += (silu(g) * u) Wd with
               g, u = rms(x, n2) Wg, rms(x, n2) Wu
  logits       rms(x, final) W_head

Every matrix product runs at ``Precision.HIGHEST``: float32 on the TPU's
MXU is otherwise rounded to bfloat16.

``control=True`` computes the same forward in float8 (e4m3): every
operand of every matrix product, weights and activations, is rounded to
float8 with a per-tensor (weights) or per-row (activations) scale, and
accumulated in float32.  That is the control of the correctness check: a
lower precision than the configuration states has to fail it.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 128          # queries per attention block
V_BLOCK = 8192         # at most this many vocabulary columns per head block
F8_MAX = 448.0         # largest finite float8_e4m3fn


def _fp8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, w, control, spec="...d,df->...f"):
    """Activation a [..., d] times weight w [d, f]."""
    if control:
        a, w = _fp8(a, -1), _fp8(w, None)
    return jnp.einsum(spec, a, w, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def _rope(x, pos, theta):
    """x [S, heads, Dh]; rotate-half rope at positions pos [S]."""
    half = x.shape[-1] // 2
    inv = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                  * 2.0 / x.shape[-1])
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, control):
    """Causal GQA attention.  q [S, H, Dh]; k, v [S, Kh, Dh]."""
    S, H, Dh = q.shape
    Kh = k.shape[1]
    G = H // Kh
    qg = q.reshape(S, Kh, G, Dh) / math.sqrt(Dh)
    if control:
        qg, k, v = _fp8(qg, -1), _fp8(k, -1), _fp8(v, -1)
    n_blocks = S // Q_BLOCK
    kpos = jnp.arange(S)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(qg, i * Q_BLOCK, Q_BLOCK, 0)
        s = jnp.einsum("qkgd,skd->kgqs", qb, k, precision=HIGHEST)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if control:
            p = _fp8(p, -1)
        return jnp.einsum("kgqs,skd->qkgd", p, v, precision=HIGHEST)

    o = jax.lax.map(block, jnp.arange(n_blocks))      # [n, Qb, Kh, G, Dh]
    return o.reshape(S, H * Dh)


def _layer_one(x, w, dims, control):
    """One decoder layer for one request's rows x [S, d]."""
    H, Kh, Dh, eps, theta = dims
    S = x.shape[0]
    pos = jnp.arange(S)
    h = _rms(x, w["norm1"], eps)
    q = _rope(_mm(h, w["wq"], control).reshape(S, H, Dh), pos, theta)
    k = _rope(_mm(h, w["wk"], control).reshape(S, Kh, Dh), pos, theta)
    v = _mm(h, w["wv"], control).reshape(S, Kh, Dh)
    x = x + _mm(_attention(q, k, v, control), w["wo"], control)
    h = _rms(x, w["norm2"], eps)
    g = _mm(h, w["w_gate"], control)
    u = _mm(h, w["w_up"], control)
    return x + _mm(jax.nn.silu(g) * u, w["w_down"], control)


@functools.partial(jax.jit, static_argnames=("dims", "control"),
                   donate_argnums=(0,))
def _layer(h, w, *, dims, control):
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    return jax.lax.map(lambda x: _layer_one(x, w, dims, control), h)


@functools.partial(jax.jit, static_argnames=("n_img", "control"))
def _embed(tokens, images, embed, w1, w2, *, n_img, control):
    """Rows of every request: projected image rows, then token rows.
    tokens [R, S - n_img] int32; images [R, n_img, d] or None."""
    x = embed[tokens].astype(jnp.float32)
    if not n_img:
        return x
    w1, w2 = w1.astype(jnp.float32), w2.astype(jnp.float32)

    def project(m):
        g = jax.nn.gelu(_mm(m.astype(jnp.float32), w1, control),
                        approximate=True)
        return _mm(g, w2, control)

    return jnp.concatenate([jax.lax.map(project, images), x], axis=1)


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def _head(h, pos, final_norm, head, *, eps, control):
    """Logits at positions pos [R, K] of h [R, S, d], streamed over
    vocabulary blocks.  Returns (best logit [R, K], argmax [R, K])."""
    hs = jnp.take_along_axis(h, pos[..., None], axis=1)
    hs = _rms(hs, final_norm, eps)
    V = head.shape[1]
    vb = max(b for b in range(1, min(V, V_BLOCK) + 1) if V % b == 0)
    n_blocks = V // vb

    def body(carry, i):
        best, arg = carry
        w = jax.lax.dynamic_slice_in_dim(head, i * vb, vb, 1)
        lg = _mm(hs, w.astype(jnp.float32), control)
        b_val, b_arg = jnp.max(lg, -1), jnp.argmax(lg, -1) + i * vb
        take = b_val > best
        return (jnp.where(take, b_val, best),
                jnp.where(take, b_arg, arg)), None

    init = (jnp.full(pos.shape, -jnp.inf), jnp.zeros(pos.shape, jnp.int32))
    (best, arg), _ = jax.lax.scan(body, init, jnp.arange(n_blocks))
    return best, arg


@functools.partial(jax.jit, static_argnames=("eps",))
def _logits_of(h, pos, tok, final_norm, head, *, eps):
    """f32 logits of tokens tok [R, K] at positions pos [R, K]."""
    hs = _rms(jnp.take_along_axis(h, pos[..., None], axis=1), final_norm,
              eps)
    w = head[:, tok].astype(jnp.float32)                # [d, R, K]
    return jnp.einsum("rkd,drk->rk", hs, w, precision=HIGHEST)


def hidden(config: dict, params, tokens, images, *, control=False):
    """Final hidden rows [R, S, d] of R requests (right-padded)."""
    dims = (config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], config["rms_norm_eps"],
            float(config["rope_theta"]))
    n_img = 0 if images is None else images.shape[1]
    h = _embed(tokens, images, params["embed"], params["media_proj_w1"],
               params["media_proj_w2"], n_img=n_img, control=control)
    for w in params["layers"]:
        h = _layer(h, w, dims=dims, control=control)
    return h


def batch(requests, *, seq_len: int, reads: int, group: int):
    """Pack checked requests into the reference's fixed shapes, in calls of
    ``group`` requests (the last call is filled with repeats, not read).

    Each request is a dict with ``prompt`` (int32), ``image`` ([n, d] or
    None) and ``served`` (the tokens the program produced).  Its rows are
    image + prompt + served[:-1]; the served token j is read at row
    n_img + len(prompt) - 1 + j.  Returns (tokens [R, seq_len - n_img],
    images [R, n_img, d] | None, pos [R, reads], tok [R, reads],
    valid [R, reads]) with R a multiple of ``group``."""
    R = -(-len(requests) // group) * group
    first = requests[0]
    n_img = 0 if first["image"] is None else first["image"].shape[0]
    tokens = np.zeros((R, seq_len - n_img), np.int32)
    pos = np.zeros((R, reads), np.int32)
    tok = np.zeros((R, reads), np.int32)
    valid = np.zeros((R, reads), bool)
    images = None if not n_img else np.zeros(
        (R,) + first["image"].shape, first["image"].dtype)
    for r in range(R):
        q = requests[min(r, len(requests) - 1)]
        seq = np.concatenate([q["prompt"], q["served"][:-1]]).astype(np.int32)
        tokens[r, :len(seq)] = seq
        k = len(q["served"])
        pos[r, :k] = n_img + len(q["prompt"]) - 1 + np.arange(k)
        tok[r, :k] = q["served"]
        valid[r, :k] = r < len(requests)
        if n_img:
            images[r] = q["image"]
    return tokens, images, pos, tok, valid


def logit_gaps(config: dict, params, requests, *, seq_len: int, reads: int,
               group: int, control: bool = False):
    """Per checked token: the reference's best logit minus its logit of
    the token being judged.  Without ``control`` that token is the one the
    program served; with it, the one the float8 forward puts first at the
    same position.  Runs ``group`` requests per call; returns a flat array
    over the served tokens."""
    tokens, images, pos, tok, valid = batch(requests, seq_len=seq_len,
                                            reads=reads, group=group)
    eps = config["rms_norm_eps"]
    gaps = []
    for i in range(0, tokens.shape[0], group):
        sl = slice(i, i + group)
        t, p = jnp.asarray(tokens[sl]), jnp.asarray(pos[sl])
        im = None if images is None else jnp.asarray(images[sl])
        h = hidden(config, params, t, im)
        best, _ = _head(h, p, params["final_norm"], params["lm_head"],
                        eps=eps, control=False)
        if control:
            hc = hidden(config, params, t, im, control=True)
            _, judged = _head(hc, p, params["final_norm"], params["lm_head"],
                              eps=eps, control=True)
            del hc
        else:
            judged = jnp.asarray(tok[sl])
        got = _logits_of(h, p, judged, params["final_norm"],
                         params["lm_head"], eps=eps)
        del h
        gaps.append(np.asarray(best - got)[valid[sl]])
    return np.concatenate(gaps)
