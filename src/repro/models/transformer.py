"""Decoder stacks: dense / MoE / hybrid (attn+SSM) / xLSTM families.

Layer parameters are stacked on a leading axis and the stack is a single
`lax.scan` over layers with `jax.checkpoint` on the body (activation
rematerialization) — HLO size and compile time are depth-independent.
"""
from __future__ import annotations

import warnings
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.layers import (
    apply_norm,
    cross_entropy,
    dense_init,
    embed_init,
    mlp_apply,
    mlp_init,
    norm_init,
    rmsnorm,
    stacked,
)
from repro.sharding.api import constrain


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def layer_init(rng, cfg):
    r = jax.random.split(rng, 5)
    d = cfg.d_model
    p: Dict[str, Any] = {
        "norm1": norm_init(cfg, d),
        "norm2": norm_init(cfg, d),
        "attn": attn.attn_init(r[0], cfg, d),
    }
    if cfg.is_moe:
        p["moe"] = moe_mod.moe_init(r[1], cfg, d)
    elif cfg.d_ff:
        p["mlp"] = mlp_init(r[1], cfg, d, cfg.d_ff)
    if cfg.hybrid_parallel_ssm:
        p["ssm"] = ssm_mod.ssm_init(r[2], cfg, d)
        # per-branch output norms for the hybrid fusion (Hymba eq. 2)
        p["attn_out_norm"] = {"scale": jnp.zeros((d,), jnp.float32)}
        p["ssm_out_norm"] = {"scale": jnp.zeros((d,), jnp.float32)}
    return p


def init_params(rng, cfg):
    r = jax.random.split(rng, 6)
    dt = jnp.dtype(cfg.param_dtype)
    p: Dict[str, Any] = {
        "embed": embed_init(r[0], cfg.vocab_size, cfg.d_model, dt),
        "final_norm": norm_init(cfg, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(r[1], cfg.d_model, cfg.vocab_size, dt)
    if cfg.learned_pos:
        # extended learned-position range: covers the largest non-decode
        # assigned shape (32k); whisper's native 448 limit is documented in
        # configs/whisper_medium.py and decode shapes are skipped for it.
        max_pos = max(cfg.encoder_seq, 2048 if cfg.family == "toy" else 32768)
        p["pos_embed"] = embed_init(r[4], max_pos, cfg.d_model, dt)
    if cfg.family == "ssm":  # xLSTM
        pat = cfg.xlstm_pattern
        n_super = cfg.num_layers // len(pat)
        n_m = pat.count("m")
        n_s = pat.count("s")
        sub = jax.random.split(r[2], 4)
        p["xlstm"] = {
            "m_norm": stacked(sub[0], n_super * n_m, lambda k: norm_init(cfg, cfg.d_model)),
            "m": stacked(sub[1], n_super * n_m, xlstm_mod.mlstm_init, cfg, cfg.d_model),
            "s_norm": stacked(sub[2], n_super * n_s, lambda k: norm_init(cfg, cfg.d_model)),
            "s": stacked(sub[3], n_super * n_s, xlstm_mod.slstm_init, cfg, cfg.d_model),
        }
        # reshape stacks to [n_super, n_per_super, ...] for the nested scan
        p["xlstm"] = jax.tree.map(
            lambda x: x.reshape((n_super, x.shape[0] // n_super) + x.shape[1:])
            if x.shape[0] != n_super else x[:, None],
            p["xlstm"],
        )
    else:
        p["layers"] = stacked(r[3], cfg.num_layers, layer_init, cfg)
    if cfg.vision_dim:
        p["vision_proj"] = dense_init(r[5], cfg.vision_dim, cfg.d_model, dt)
    return p


# ---------------------------------------------------------------------------
# forward (training / prefill)
# ---------------------------------------------------------------------------


def _hybrid_fuse(cfg, p, a_out, s_out):
    a = rmsnorm(a_out, p["attn_out_norm"]["scale"])
    s = rmsnorm(s_out, p["ssm_out_norm"]["scale"])
    return 0.5 * (a + s)


def layer_apply(cfg, lp, h, positions, impl="auto", window=None):
    aux = jnp.zeros((), jnp.float32)
    hn = apply_norm(cfg, lp["norm1"], h)
    a_out = attn.attention_block(cfg, lp["attn"], hn, positions, impl=impl, window=window)
    if cfg.hybrid_parallel_ssm:
        s_out, _ = ssm_mod.ssm_apply(cfg, lp["ssm"], hn)
        h = h + _hybrid_fuse(cfg, lp, a_out, s_out)
    else:
        h = h + a_out
    h = constrain(h, "batch", None, "embed")
    hn2 = apply_norm(cfg, lp["norm2"], h)
    if cfg.is_moe:
        y, aux = moe_mod.moe_apply(cfg, lp["moe"], hn2)
        h = h + y
    elif cfg.d_ff:
        h = h + mlp_apply(cfg, lp["mlp"], hn2)
    return constrain(h, "batch", None, "embed"), aux


def embed_tokens(cfg, p, batch):
    tokens = batch["tokens"]
    h = p["embed"][tokens].astype(jnp.dtype(cfg.compute_dtype))
    if cfg.vision_dim and "patches" in batch:
        pe = (batch["patches"] @ p["vision_proj"]).astype(h.dtype)
        np_ = pe.shape[1]
        h = jnp.concatenate([pe, h[:, np_:]], axis=1) if np_ <= h.shape[1] else h
    if cfg.learned_pos:
        S = h.shape[1]
        h = h + p["pos_embed"][:S][None].astype(h.dtype)
    return h


def unembed(cfg, p, h):
    h = apply_norm(cfg, p["final_norm"], h)
    w = p["embed"].T if cfg.tie_embeddings else p["lm_head"]
    logits = h @ w
    return constrain(logits, "batch", None, "vocab")


def _remat_wrap(body, remat):
    """remat: True (full recompute) | False | "dots" (save matmul outputs —
    jax.checkpoint_policies.dots_with_no_batch_dims_saveable)."""
    if remat is True:
        return jax.checkpoint(body)
    if remat == "dots":
        return jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    return body


def forward(cfg, p, batch, impl="auto", window=None, remat=True, unroll=1):
    """-> (logits [B,S,V], aux_loss). Decoder-only families."""
    h = embed_tokens(cfg, p, batch)
    S = h.shape[1]
    positions = jnp.arange(S)

    if cfg.family == "ssm":
        h = _xlstm_stack(cfg, p["xlstm"], h, remat=remat, unroll=unroll)
        return unembed(cfg, p, h), jnp.zeros((), jnp.float32)

    def body(carry, lp):
        h, aux = carry
        h, a = layer_apply(cfg, lp, h, positions, impl=impl, window=window)
        return (h, aux + a), None

    body_fn = _remat_wrap(body, remat)
    (h, aux), _ = jax.lax.scan(
        body_fn, (h, jnp.zeros((), jnp.float32)), p["layers"], unroll=unroll
    )
    return unembed(cfg, p, h), aux / max(cfg.num_layers, 1)


def _xlstm_stack(cfg, xp, h, remat=True, unroll=1):  # noqa: D401
    """Scan over super-blocks; the inner mLSTM/sLSTM runs are fully
    unrolled (<= 7 bodies) so per-super-block cost is exact in the HLO cost
    model; the outer scan takes the two-point `unroll` knob (dry-run)."""

    def super_block(h, sp):
        def m_body(h, mp):
            hn = apply_norm(cfg, mp["norm"], h)
            y, _ = xlstm_mod.mlstm_apply(cfg, mp["p"], hn)
            return h + y, None

        h, _ = jax.lax.scan(m_body, h, {"norm": sp["m_norm"], "p": sp["m"]},
                            unroll=True)

        def s_body(h, spp):
            hn = apply_norm(cfg, spp["norm"], h)
            y, _ = xlstm_mod.slstm_apply(cfg, spp["p"], hn)
            return h + y, None

        h, _ = jax.lax.scan(s_body, h, {"norm": sp["s_norm"], "p": sp["s"]},
                            unroll=True)
        return h, None

    blk = _remat_wrap(super_block, remat)
    h, _ = jax.lax.scan(blk, h, xp, unroll=unroll)
    return h


def loss_fn(cfg, p, batch, impl="auto", window=None, remat=True, unroll=1):
    logits, aux = forward(cfg, p, batch, impl=impl, window=window, remat=remat,
                          unroll=unroll)
    ce = cross_entropy(logits, batch["targets"], batch.get("loss_mask"))
    return ce + aux, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# serving: prefill + single-token decode
# ---------------------------------------------------------------------------


class DecodeCache(NamedTuple):
    kv: Optional[attn.KVCache]  # leaves stacked [L, ...]
    ssm: Optional[ssm_mod.SSMState]  # hybrid only, stacked [L, ...]
    xlstm_m: Optional[xlstm_mod.MLSTMState]  # [n_super, n_m, ...]
    xlstm_s: Optional[xlstm_mod.SLSTMState]  # [n_super, n_s, ...]


def init_cache(cfg, batch: int, seq_len: int, window: int = 0) -> DecodeCache:
    kv = ssm_st = xm = xs = None
    if cfg.family == "ssm":
        pat = cfg.xlstm_pattern
        n_super = cfg.num_layers // len(pat)
        xm = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_super, pat.count("m")) + x.shape),
            xlstm_mod.init_mlstm_state(cfg, batch, cfg.d_model),
        )
        xs = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_super, pat.count("s")) + x.shape),
            xlstm_mod.init_slstm_state(cfg, batch, cfg.d_model),
        )
    else:
        W = window or cfg.sliding_window
        one = attn.init_kv_cache(cfg, batch, seq_len, window=W)
        kv = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (cfg.num_layers,) + x.shape), one)
        if cfg.hybrid_parallel_ssm:
            st = ssm_mod.init_ssm_state(cfg, batch, cfg.d_model, dtype=cfg.param_dtype)
            ssm_st = jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (cfg.num_layers,) + x.shape), st
            )
    return DecodeCache(kv=kv, ssm=ssm_st, xlstm_m=xm, xlstm_s=xs)


def _row_select(active, new, old):
    """Per-batch-row select: new where active else old. Batch axis leads."""
    B = active.shape[0]
    return jnp.where(active.reshape((B,) + (1,) * (new.ndim - 1)), new, old)


def decode_step(cfg, p, cache: DecodeCache, token, pos, window: int = 0, unroll=1,
                cache_update: str = "mask", active=None):
    """token [B] int32, pos [B] int32 -> (logits [B, V], new cache).

    active: optional bool [B] slot mask (serve/ continuous batching) —
    inactive rows leave EVERY cache leaf (KV, SSM state, xLSTM state)
    bit-identical and, for MoE layers, never compete for expert capacity;
    their logits are garbage and must be ignored by the caller.
    """
    B = token.shape[0]
    h = p["embed"][token][:, None].astype(jnp.dtype(cfg.compute_dtype))  # [B,1,d]
    if cfg.learned_pos:
        h = h + p["pos_embed"][pos][:, None].astype(h.dtype)

    if cfg.family == "ssm":
        h, xm, xs = _xlstm_decode(cfg, p["xlstm"], h, cache, unroll=unroll)
        if active is not None:  # batch axis is 2: [n_super, n_per, B, ...]
            sel = lambda n, o: jnp.where(  # noqa: E731
                active.reshape((1, 1, B) + (1,) * (n.ndim - 3)), n, o)
            xm = jax.tree.map(sel, xm, cache.xlstm_m)
            xs = jax.tree.map(sel, xs, cache.xlstm_s)
        logits = unembed(cfg, p, h)[:, 0]
        return logits, DecodeCache(None, None, xm, xs)

    W = window or cfg.sliding_window

    def body(carry, xs_):
        h = carry
        lp, kv_l, ssm_l = xs_
        hn = apply_norm(cfg, lp["norm1"], h)
        a_out, kv_new = attn.decode_attention_block(cfg, lp["attn"], hn, kv_l, pos,
                                                     window=W, cache_update=cache_update,
                                                     active=active)
        new_ssm = ssm_l
        if cfg.hybrid_parallel_ssm:
            s_out, new_ssm = ssm_mod.ssm_apply(cfg, lp["ssm"], hn, ssm_l)
            if active is not None:
                new_ssm = jax.tree.map(
                    lambda n, o: _row_select(active, n, o), new_ssm, ssm_l)
            h = h + _hybrid_fuse(cfg, lp, a_out, s_out)
        else:
            h = h + a_out
        hn2 = apply_norm(cfg, lp["norm2"], h)
        if cfg.is_moe:
            tm = None if active is None else active[:, None]
            y, _ = moe_mod.moe_apply(cfg, lp["moe"], hn2, token_mask=tm)
            h = h + y
        elif cfg.d_ff:
            h = h + mlp_apply(cfg, lp["mlp"], hn2)
        return h, (kv_new, new_ssm)

    h, (kv, ssm_st) = jax.lax.scan(body, h, (p["layers"], cache.kv, cache.ssm),
                                   unroll=unroll)
    logits = unembed(cfg, p, h)[:, 0]
    return logits, DecodeCache(kv=kv, ssm=ssm_st, xlstm_m=None, xlstm_s=None)


# ---------------------------------------------------------------------------
# paged KV decode (DESIGN.md §12): pooled pages + per-slot page table
# ---------------------------------------------------------------------------


class PagedDecodeCache(NamedTuple):
    """Pooled-capacity decode cache: KV pages are shared across slots.

    ``kv`` is a :class:`attn.PagedKVPool` with leaves stacked [L, n_pages,
    page_size, Hkv, hd] — ONE page id addresses the same page in every
    layer, so the (host-owned) page table is shared across layers and
    passed per dispatch, not stored here. Hybrid models keep their O(1)
    per-slot SSM state rows dense ([L, n_slots, ...]) — recurrent state
    has nothing to page.
    """

    kv: Optional[attn.PagedKVPool]  # leaves stacked [L, ...]
    ssm: Optional[ssm_mod.SSMState]  # hybrid only, stacked [L, n_slots, ...]


def init_paged_cache(cfg, n_slots: int, n_pages: int,
                     page_size: int) -> PagedDecodeCache:
    """Shared pool of ``n_pages * page_size`` KV rows for ``n_slots`` slots.

    Recurrent-only families (xLSTM) have no KV to page — use the
    contiguous :func:`init_cache` / :func:`decode_step` path for them.
    """
    if cfg.family == "ssm":
        raise ValueError(
            f"{cfg.name}: family='ssm' keeps O(1) recurrent state per slot "
            "— there is no KV cache to page; use init_cache/decode_step")
    one = attn.init_paged_kv_pool(cfg, n_pages, page_size)
    kv = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (cfg.num_layers,) + x.shape), one)
    ssm_st = None
    if cfg.hybrid_parallel_ssm:
        st = ssm_mod.init_ssm_state(cfg, n_slots, cfg.d_model,
                                    dtype=cfg.param_dtype)
        ssm_st = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (cfg.num_layers,) + x.shape), st)
    return PagedDecodeCache(kv=kv, ssm=ssm_st)


def paged_decode_step(cfg, p, cache: PagedDecodeCache, page_table, token, pos,
                      window: int = 0, unroll=1, cache_update: str = "mask",
                      active=None):
    """token [B], pos [B], page_table [B, P] int32 -> (logits [B, V],
    new cache). The paged sibling of :func:`decode_step`: same layer scan,
    same masked no-op guarantees for inactive rows (KV write, SSM state,
    MoE capacity), but KV lives in the shared page pool and each slot's
    cache is reached through its page-table row.
    """
    B = token.shape[0]
    h = p["embed"][token][:, None].astype(jnp.dtype(cfg.compute_dtype))
    if cfg.learned_pos:
        h = h + p["pos_embed"][pos][:, None].astype(h.dtype)

    W = window or cfg.sliding_window

    def body(carry, xs_):
        h = carry
        lp, kv_l, ssm_l = xs_
        hn = apply_norm(cfg, lp["norm1"], h)
        a_out, kv_new = attn.paged_decode_attention_block(
            cfg, lp["attn"], hn, kv_l, page_table, pos, window=W,
            cache_update=cache_update, active=active)
        new_ssm = ssm_l
        if cfg.hybrid_parallel_ssm:
            s_out, new_ssm = ssm_mod.ssm_apply(cfg, lp["ssm"], hn, ssm_l)
            if active is not None:
                new_ssm = jax.tree.map(
                    lambda n, o: _row_select(active, n, o), new_ssm, ssm_l)
            h = h + _hybrid_fuse(cfg, lp, a_out, s_out)
        else:
            h = h + a_out
        hn2 = apply_norm(cfg, lp["norm2"], h)
        if cfg.is_moe:
            tm = None if active is None else active[:, None]
            y, _ = moe_mod.moe_apply(cfg, lp["moe"], hn2, token_mask=tm)
            h = h + y
        elif cfg.d_ff:
            h = h + mlp_apply(cfg, lp["mlp"], hn2)
        return h, (kv_new, new_ssm)

    h, (kv, ssm_st) = jax.lax.scan(body, h, (p["layers"], cache.kv, cache.ssm),
                                   unroll=unroll)
    logits = unembed(cfg, p, h)[:, 0]
    return logits, PagedDecodeCache(kv=kv, ssm=ssm_st)


class KernelExtendFallbackWarning(UserWarning):
    """Chunk prefill lowered ``cache_update="kernel"`` to the mask path.

    The Pallas prefill-insert kernel has no chunk/suffix variant yet —
    a ``cache_update="kernel"`` extend path is the open §12.2 follow-up
    (ROADMAP.md, serving-scheduler item). Decode still dispatches the
    Pallas kernel; only the chunk WRITES take the one-hot mask path,
    which is bit-identical (tests/test_serve_sched.py pins parity).
    """


_KERNEL_EXTEND_WARNED = False


def warn_kernel_extend_fallback(site: str) -> None:
    """One-time (per process) structured warning for the kernel->mask
    chunk-prefill lowering; every lowering site routes through here so
    the notice fires once no matter which plane hits it first."""
    global _KERNEL_EXTEND_WARNED
    if _KERNEL_EXTEND_WARNED:
        return
    _KERNEL_EXTEND_WARNED = True
    warnings.warn(
        KernelExtendFallbackWarning(
            f"{site}: cache_update='kernel' has no chunk-prefill variant "
            "yet — chunk writes lowered to the bit-identical 'mask' path "
            "(decode keeps the Pallas kernel). Tracked as the §12.2 "
            "follow-up: a cache_update='kernel' extend path (ROADMAP.md)."),
        stacklevel=3)


def paged_prefill_chunk(cfg, p, cache: PagedDecodeCache, page_row, tokens,
                        start, length, unroll=1, cache_update: str = "mask"):
    """Prefill one chunk of a single request's prompt DIRECTLY into the
    paged pool (serve/ prefix caching + chunked prefill; DESIGN.md §12.2).

    tokens [1, C] covers absolute positions ``[start, start + length)``
    of the slot whose page-table row is ``page_row`` [P]; rows >= length
    are padding (never written). start/length are traced int32 scalars —
    one compile per chunk WIDTH C. Returns (logits [1, V] at position
    ``start + length - 1``, new cache): the logits only matter for the
    FINAL chunk of a prompt, where they produce the first generated
    token exactly like a monolithic prefill.

    Earlier context (previous chunks, prefix-cached shared pages) is
    read back from the pool; param_dtype == compute_dtype makes that
    roundtrip the identity, so chunked streams are bit-identical to the
    monolithic prefill path. Full-attention KV-only models ONLY:
    recurrent state (SSM / hybrid) absorbs the whole prompt at once and
    cannot resume from pool pages; the SWA ring wraps writes into early
    pages that chunk boundaries would tear.
    """
    if cfg.family == "ssm" or cfg.hybrid_parallel_ssm:
        raise ValueError(
            f"{cfg.name}: recurrent state cannot be chunk-prefilled — "
            "the SSM carry does not live in pool pages")
    if cfg.sliding_window:
        raise ValueError(
            f"{cfg.name}: chunked prefill is full-attention only — the SWA "
            "ring wraps KV writes into early (possibly shared) pages")
    B, C = tokens.shape
    positions = start + jnp.arange(C, dtype=jnp.int32)
    h = p["embed"][tokens].astype(jnp.dtype(cfg.compute_dtype))  # [1, C, d]
    if cfg.learned_pos:
        h = h + p["pos_embed"][positions][None].astype(h.dtype)
    # pad rows must not compete for MoE expert capacity
    live = (jnp.arange(C, dtype=jnp.int32) < length)[None, :]  # [1, C]
    if cache_update == "kernel":
        warn_kernel_extend_fallback("models.transformer.paged_prefill_chunk")
    cu = "mask" if cache_update == "kernel" else cache_update

    def body(carry, xs_):
        h = carry
        lp, kv_l = xs_
        hn = apply_norm(cfg, lp["norm1"], h)
        a_out, kv_new = attn.paged_prefill_attention_block(
            cfg, lp["attn"], hn, kv_l, page_row, start, length,
            cache_update=cu)
        h = h + a_out
        hn2 = apply_norm(cfg, lp["norm2"], h)
        if cfg.is_moe:
            y, _ = moe_mod.moe_apply(cfg, lp["moe"], hn2, token_mask=live)
            h = h + y
        elif cfg.d_ff:
            h = h + mlp_apply(cfg, lp["mlp"], hn2)
        return h, kv_new

    h, kv = jax.lax.scan(body, h, (p["layers"], cache.kv), unroll=unroll)
    last = jnp.take_along_axis(
        h, jnp.maximum(length - 1, 0).reshape(1, 1, 1), axis=1)  # [1,1,d]
    logits = unembed(cfg, p, last)[:, 0]
    return logits, PagedDecodeCache(kv=kv, ssm=cache.ssm)


def insert_cache_pages(cache: PagedDecodeCache, one: DecodeCache, slot,
                       page_ids, cache_update: str = "mask") -> PagedDecodeCache:
    """Page-granular admission: write one request's prefill cache (batch 1)
    into its allocated pool pages ``page_ids`` [P] (-1 = unallocated,
    skipped) and — for hybrid models — its SSM state into row ``slot``.
    The prefill cache is zero-padded up to P * page_size rows so every
    allocated page is overwritten in full (see attn.insert_kv_pages).

    cache_update="kernel" uses the layer-stacked kernels/paged_attention
    routed block-write (grid over layers x slot pages — one launch for
    the whole stack, only the slot's own pages touched) instead of the
    per-layer full-pool jnp.where; pool bits are identical.
    """
    L, N, ps = cache.kv.k.shape[0], cache.kv.k.shape[1], cache.kv.k.shape[2]
    Hkv, hd = cache.kv.k.shape[3], cache.kv.k.shape[4]
    P = page_ids.shape[0]
    cap, have = P * ps, one.kv.k.shape[2]
    one_kv = one.kv
    if have < cap:  # SWA ring of W rows with W not a page multiple
        one_kv = attn.KVCache(
            k=jnp.pad(one_kv.k, ((0, 0), (0, 0), (0, cap - have), (0, 0), (0, 0))),
            v=jnp.pad(one_kv.v, ((0, 0), (0, 0), (0, cap - have), (0, 0), (0, 0))),
            pos=one_kv.pos,
        )
    if cache_update == "kernel":
        from repro.kernels.paged_attention import ops as pa_ops

        k, v = pa_ops.paged_insert(
            cache.kv.k, cache.kv.v,
            one_kv.k[:, 0].reshape(L, P, ps, Hkv, hd),
            one_kv.v[:, 0].reshape(L, P, ps, Hkv, hd),
            page_ids)
        kv = attn.PagedKVPool(k=k, v=v)
    else:
        kv = jax.vmap(lambda pool, o: attn.insert_kv_pages(pool, o, page_ids))(
            attn.PagedKVPool(cache.kv.k, cache.kv.v),
            attn.KVCache(one_kv.k, one_kv.v,
                         jnp.zeros((one_kv.k.shape[0], 1, cap), jnp.int32)))
    ssm_st = None
    if cache.ssm is not None:  # [L, B, ...]
        B = jax.tree.leaves(cache.ssm)[0].shape[1]
        sel = (jnp.arange(B, dtype=jnp.int32) == slot)

        def write(old, new):
            s = sel.reshape((1, B) + (1,) * (old.ndim - 2))
            return jnp.where(s, new, old)

        ssm_st = jax.tree.map(write, cache.ssm, one.ssm)
    return PagedDecodeCache(kv=kv, ssm=ssm_st)


def insert_cache_slot(cache: DecodeCache, one: DecodeCache, slot) -> DecodeCache:
    """Write one request's DecodeCache (batch 1) into row `slot` of a
    B-slot cache — the serve/ admission path. Every leaf goes through the
    masked update (attn.insert_kv_slot / one-hot jnp.where), so admission
    composes with any sharding of the big cache and never recompiles.
    """

    def sel_at(axis):
        def f(old, new):
            B = old.shape[axis]
            sel = (jnp.arange(B, dtype=jnp.int32) == slot).reshape(
                (1,) * axis + (B,) + (1,) * (old.ndim - axis - 1))
            return jnp.where(sel, new, old)
        return f

    kv = ssm_st = xm = xs = None
    if cache.kv is not None:
        kv = jax.vmap(lambda c, o: attn.insert_kv_slot(c, o, slot))(cache.kv, one.kv)
    if cache.ssm is not None:  # [L, B, ...]
        ssm_st = jax.tree.map(lambda o, n: sel_at(1)(o, n), cache.ssm, one.ssm)
    if cache.xlstm_m is not None:  # [n_super, n_per, B, ...]
        xm = jax.tree.map(lambda o, n: sel_at(2)(o, n), cache.xlstm_m, one.xlstm_m)
        xs = jax.tree.map(lambda o, n: sel_at(2)(o, n), cache.xlstm_s, one.xlstm_s)
    return DecodeCache(kv=kv, ssm=ssm_st, xlstm_m=xm, xlstm_s=xs)


def _xlstm_decode(cfg, xp, h, cache: DecodeCache, unroll=1):
    def super_block(h, xs_):
        sp, m_st, s_st = xs_

        def m_body(h, t):
            mp, st = t
            hn = apply_norm(cfg, mp["norm"], h)
            y, st = xlstm_mod.mlstm_apply(cfg, mp["p"], hn, st)
            return h + y, st

        h, m_st = jax.lax.scan(m_body, h, ({"norm": sp["m_norm"], "p": sp["m"]}, m_st),
                               unroll=True)

        def s_body(h, t):
            spp, st = t
            hn = apply_norm(cfg, spp["norm"], h)
            y, st = xlstm_mod.slstm_apply(cfg, spp["p"], hn, st)
            return h + y, st

        h, s_st = jax.lax.scan(s_body, h, ({"norm": sp["s_norm"], "p": sp["s"]}, s_st),
                               unroll=True)
        return h, (m_st, s_st)

    h, (xm, xs) = jax.lax.scan(super_block, h, (xp, cache.xlstm_m, cache.xlstm_s),
                               unroll=unroll)
    return h, xm, xs


def prefill(cfg, p, batch, impl="auto", window: int = 0, pad_to: int = 0, unroll=1,
            length=None):
    """Full-prompt forward; returns (last-token logits [B,V], DecodeCache).

    `pad_to`: full-attention cache capacity (room for decoded tokens).

    `length`: optional int32 [B] true prompt lengths — tokens at positions
    >= length[b] are right-padding (serve/ prompt buckets): the returned
    logits come from position length[b]-1 and padded cache slots are
    invalidated (pos=-1). Causal masking makes this bit-identical to an
    exact-length prefill for dense layers; MoE layers route pad tokens
    BEHIND live ones (token_mask), so padding never displaces a live
    token — but the expert capacity is computed from the PADDED token
    count, so a live token the exact-length run would DROP on overflow
    can survive here (inherent to static Switch/GShard capacity). Only
    valid for pure KV-cache families: recurrent state (SSM / hybrid /
    xLSTM) absorbs padded tokens and cannot be masked after the fact.
    """
    if length is not None and (cfg.family == "ssm" or cfg.hybrid_parallel_ssm):
        raise ValueError(
            "prefill(length=) needs a KV-only cache; recurrent families "
            "must prefill at the exact prompt length")
    if length is not None and (window or cfg.sliding_window):
        raise ValueError(
            "prefill(length=) is full-attention only: the ring buffer keeps "
            "the last `window` slots of the PADDED prompt, dropping live "
            "tokens — prefill SWA models at the exact prompt length")
    h = embed_tokens(cfg, p, batch)
    B, S = h.shape[:2]
    positions = jnp.arange(S)
    W = window or cfg.sliding_window
    # pad tokens must not compete for MoE expert capacity (their garbage
    # activations would displace live tokens from the dispatch buckets)
    live = None if length is None else (positions[None, :] < length[:, None])

    if cfg.family == "ssm":
        # run the stack step-free but capture final recurrent states
        cache = init_cache(cfg, B, S)
        h2, xm, xs = _xlstm_prefill_states(cfg, p["xlstm"], h, cache)
        logits = unembed(cfg, p, h2)[:, -1]
        return logits, DecodeCache(None, None, xm, xs)

    def body(carry, lp):
        h = carry
        hn = apply_norm(cfg, lp["norm1"], h)
        # W, not `window`: window=0 means "the config's own window" here,
        # while attention_block reads 0 as full attention — an S > W prompt
        # would otherwise attend past the window that decode enforces
        a_out = attn.attention_block(cfg, lp["attn"], hn, positions, impl=impl, window=W)
        kv = attn.prefill_kv_cache(cfg, lp["attn"], hn, positions, window=W, pad_to=pad_to)
        new_ssm = None
        if cfg.hybrid_parallel_ssm:
            s_out, new_ssm = ssm_mod.ssm_apply(cfg, lp["ssm"], hn)
            h = h + _hybrid_fuse(cfg, lp, a_out, s_out)
        else:
            h = h + a_out
        hn2 = apply_norm(cfg, lp["norm2"], h)
        if cfg.is_moe:
            y, _ = moe_mod.moe_apply(cfg, lp["moe"], hn2, token_mask=live)
            h = h + y
        elif cfg.d_ff:
            h = h + mlp_apply(cfg, lp["mlp"], hn2)
        return h, (kv, new_ssm)

    h, (kv, ssm_st) = jax.lax.scan(jax.checkpoint(body), h, p["layers"],
                                   unroll=unroll)
    if length is None:
        logits = unembed(cfg, p, h)[:, -1]
    else:
        last = jnp.take_along_axis(
            h, (length - 1).astype(jnp.int32)[:, None, None], axis=1)  # [B,1,d]
        logits = unembed(cfg, p, last)[:, 0]
        kv = kv._replace(pos=jnp.where(kv.pos < length[None, :, None], kv.pos, -1))
    return logits, DecodeCache(kv=kv, ssm=ssm_st, xlstm_m=None, xlstm_s=None)


def _xlstm_prefill_states(cfg, xp, h, cache: DecodeCache):
    def super_block(h, xs_):
        sp, m_st, s_st = xs_

        def m_body(h, t):
            mp, st = t
            hn = apply_norm(cfg, mp["norm"], h)
            y, st = xlstm_mod.mlstm_apply(cfg, mp["p"], hn, st)
            return h + y, st

        h, m_st = jax.lax.scan(m_body, h, ({"norm": sp["m_norm"], "p": sp["m"]}, m_st))

        def s_body(h, t):
            spp, st = t
            hn = apply_norm(cfg, spp["norm"], h)
            y, st = xlstm_mod.slstm_apply(cfg, spp["p"], hn, st)
            return h + y, st

        h, s_st = jax.lax.scan(s_body, h, ({"norm": sp["s_norm"], "p": sp["s"]}, s_st))
        return h, (m_st, s_st)

    h, (xm, xs) = jax.lax.scan(super_block, h, (xp, cache.xlstm_m, cache.xlstm_s))
    return h, xm, xs
