"""Plain reference of the starcoder2-3b decoder (arXiv:2402.19173).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision,
one layer at a time over whole sequences, no cache, no paging, no kernels;
it imports nothing of the program. The layer follows the published
architecture: pre-LayerNorm (eps 1e-5), grouped-query attention with
biased q/k/v projections, rotate-half RoPE, a causal sliding window,
a tanh-GELU MLP with biases, a final LayerNorm and the LM head tied to
the embedding. Two departures follow the served program's parameter set
and are noted in PERF.md: the attention output projection carries no
bias, and each LayerNorm scales by ``1 + scale``.

Weights are the benchmark's, made from a key by ``init_params`` in one
jitted call (bfloat16, as served). The reference makes them again from
the same key with the same call, once the program's state is freed, and
holds no more than one layer in float32 at a time.

``logits(..., mode="int8")`` is the control: the same forward pass at the
served program's precision (bfloat16 activations and residual stream)
with every matrix product taken in int8 — weights quantized per output
column, their inputs per token, the chip's int8 path.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

BF16 = jnp.bfloat16


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    hd = d // cfg["num_attention_heads"]
    return dict(d=d, hd=hd, f=cfg["intermediate_size"],
                hq=cfg["num_attention_heads"],
                hkv=cfg["num_key_value_heads"], V=cfg["vocab_size"],
                L=cfg["num_hidden_layers"])


def init_layer(cfg: dict, key):
    """One layer's served weights (bfloat16 matrices and biases, float32
    norm parameters), in the program's layout."""
    m = dims(cfg)
    d, f, q, kv = m["d"], m["f"], m["hq"] * m["hd"], m["hkv"] * m["hd"]
    k = jax.random.split(key, 15)

    def mat(i, shape):
        return (jax.random.normal(k[i], shape, jnp.float32)
                / math.sqrt(shape[0])).astype(BF16)

    def vec(i, n, scale, dtype):
        return (jax.random.normal(k[i], (n,), jnp.float32) * scale
                ).astype(dtype)

    return {
        "norm1": {"scale": vec(0, d, 0.1, jnp.float32),
                  "bias": vec(1, d, 0.02, jnp.float32)},
        "norm2": {"scale": vec(2, d, 0.1, jnp.float32),
                  "bias": vec(3, d, 0.02, jnp.float32)},
        "attn": {"w_q": mat(4, (d, q)), "w_k": mat(5, (d, kv)),
                 "w_v": mat(6, (d, kv)), "w_o": mat(7, (q, d)),
                 "b_q": vec(8, q, 0.02, BF16), "b_k": vec(9, kv, 0.02, BF16),
                 "b_v": vec(10, kv, 0.02, BF16)},
        "mlp": {"w_up": mat(11, (d, f)), "b_up": vec(12, f, 0.02, BF16),
                "w_down": mat(13, (f, d)),
                "b_down": vec(14, d, 0.02, BF16)},
    }


def layer_key(key, l):
    return jax.random.fold_in(jax.random.fold_in(key, 1), l)


def init_top(cfg: dict, key):
    m = dims(cfg)
    ke, ks, kb = jax.random.split(jax.random.fold_in(key, 0), 3)
    return {
        "embed": (jax.random.normal(ke, (m["V"], m["d"]), jnp.float32)
                  * 0.02).astype(BF16),
        "final_norm": {
            "scale": jax.random.normal(ks, (m["d"],), jnp.float32) * 0.1,
            "bias": jax.random.normal(kb, (m["d"],), jnp.float32) * 0.02},
    }


def init_params(cfg: dict, key):
    """The whole served parameter set; layers stacked on a leading axis,
    made one layer at a time (``lax.map``) to bound the transient memory."""
    top = init_top(cfg, key)
    top["layers"] = jax.lax.map(
        lambda l: init_layer(cfg, layer_key(key, l)),
        jnp.arange(cfg["num_hidden_layers"]))
    return top


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _layernorm(x, p, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * (1.0 + p["scale"]) + p["bias"]


def _rope(x, pos, theta):
    """x [S, H, hd]; rotate-half RoPE at integer positions ``pos`` [S]."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _quant(w, axis):
    """Symmetric int8 along ``axis`` (the scale is shared across it),
    returned dequantized in bfloat16."""
    w = w.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=axis, keepdims=True),
                    1e-12) / 127.0
    return (jnp.clip(jnp.round(w / s), -127, 127) * s).astype(BF16)


def _mm(x, w, mode):
    if mode == "f32":
        return x @ w.astype(jnp.float32)
    return _quant(x, -1) @ _quant(w, 0)


def _layer(cfg, lp, h, mode, q_block=512):
    """One decoder layer over a whole sequence h [S, d]; the residual
    stream is float32, or bfloat16 in the int8 control."""
    m = dims(cfg)
    dt = jnp.float32 if mode == "f32" else BF16
    lp = jax.tree.map(lambda v: v.astype(jnp.float32) if mode == "f32"
                      or v.dtype != BF16 else v, lp)
    W = cfg.get("sliding_window") or h.shape[0]
    S = h.shape[0]
    pos = jnp.arange(S)
    x = _layernorm(h.astype(jnp.float32), lp["norm1"]).astype(dt)
    a = lp["attn"]
    q = (_mm(x, a["w_q"], mode) + a["b_q"]).reshape(S, m["hq"], m["hd"])
    k = (_mm(x, a["w_k"], mode) + a["b_k"]).reshape(S, m["hkv"], m["hd"])
    v = (_mm(x, a["w_v"], mode) + a["b_v"]).reshape(S, m["hkv"], m["hd"])
    theta = cfg["rope_theta"]
    q = _rope(q.astype(jnp.float32), pos, theta).astype(dt)
    k = _rope(k.astype(jnp.float32), pos, theta).astype(dt)
    G = m["hq"] // m["hkv"]
    outs = []
    for s0 in range(0, S, q_block):
        qb = q[s0:s0 + q_block].reshape(-1, m["hkv"], G, m["hd"])
        qp = pos[s0:s0 + q_block]
        logit = jnp.einsum("qhgd,khd->hgqk", qb, k).astype(jnp.float32) \
            / math.sqrt(m["hd"])
        ok = (pos[None, :] <= qp[:, None]) & (pos[None, :] > qp[:, None] - W)
        w = jax.nn.softmax(jnp.where(ok, logit, -jnp.inf), axis=-1)
        o = jnp.einsum("hgqk,khd->qhgd", w.astype(dt), v)
        outs.append(o.reshape(-1, m["hq"] * m["hd"]))
    o = jnp.concatenate(outs, 0)
    h = h + _mm(o, a["w_o"], mode).astype(h.dtype)
    x = _layernorm(h.astype(jnp.float32), lp["norm2"]).astype(dt)
    u = jax.nn.gelu((_mm(x, lp["mlp"]["w_up"], mode) + lp["mlp"]["b_up"])
                    .astype(jnp.float32), approximate=True).astype(dt)
    y = _mm(u, lp["mlp"]["w_down"], mode) + lp["mlp"]["b_down"]
    return h + y.astype(h.dtype)


def logits(cfg: dict, key, seqs, positions, mode: str = "f32"):
    """Logits [len(positions[i]), V] (float32) of each token sequence
    ``seqs[i]`` at the positions asked, computed layer by layer.

    Sequences are padded at the end to a multiple of 1024 tokens (causal
    attention makes the padding invisible to every real position), so a
    few compiled shapes serve any lengths.
    """
    prec = "highest" if mode == "f32" else "default"
    dt = jnp.float32 if mode == "f32" else BF16
    params = jax.jit(lambda k: init_params(cfg, k))(key)
    emb = params["embed"]
    hs = []
    for s in seqs:
        n = -(-len(s) // 1024) * 1024
        ids = np.zeros(n, np.int32)
        ids[:len(s)] = s
        hs.append(emb[jnp.asarray(ids)].astype(dt))
    @jax.jit
    def step(lp, h):
        with jax.default_matmul_precision(prec):
            return _layer(cfg, lp, h, mode)

    for l in range(cfg["num_hidden_layers"]):
        lp = jax.tree.map(lambda x: x[l], params["layers"])
        hs = [step(lp, h) for h in hs]
    out = []
    with jax.default_matmul_precision(prec):
        for h, p in zip(hs, positions):
            x = _layernorm(h[jnp.asarray(p)].astype(jnp.float32),
                           params["final_norm"]).astype(dt)
            out.append(np.asarray(_mm(x, emb.T, mode).astype(jnp.float32)))
    return out


def served_gaps(cfg: dict, key, items, mode: str = "f32"):
    """Gaps by which a served token's reference logit lies below the
    reference's best, over every served token of ``items`` [(prompt ids,
    served ids)]. With ``mode="int8"`` the tokens judged are instead
    those the int8 control puts first at the same positions.
    -> (widest gap, mean gap, tokens judged)"""
    seqs, pos = [], []
    for prompt, served in items:
        seqs.append(np.concatenate([prompt, served[:-1]]).astype(np.int32))
        pos.append(np.arange(len(prompt) - 1, len(prompt) + len(served) - 1))
    ref = logits(cfg, key, seqs, pos, "f32")
    if mode == "f32":
        picks = [np.asarray(s) for _, s in items]
    else:
        picks = [np.argmax(lg, -1) for lg in logits(cfg, key, seqs, pos, mode)]
    gaps = np.concatenate([
        lg.max(-1) - np.take_along_axis(lg, np.asarray(pk)[:, None], -1)[:, 0]
        for lg, pk in zip(ref, picks)])
    return float(gaps.max()), float(gaps.mean()), int(gaps.size)
