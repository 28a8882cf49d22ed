"""Parity suite for kernels/paged_attention vs the XLA mask/scatter
oracles (interpret mode on CPU; the compile path is accelerator-gated).

The bars, per DESIGN.md §7:
  * pool contents BITWISE equal — both sides write the k_new/v_new rows
    verbatim, so there is no tolerance to hide a mis-routed page behind;
  * attention outputs to tight allclose — the kernel's online softmax
    reassociates the fp32 reduction, so ULP-level differences vs the
    dense full-softmax oracle are expected and bounded;
  * greedy token streams through PagedServeLoop bit-identical to the
    "mask" path end to end (argmax is insensitive to the ULP noise).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.paged_attention import kernel as pa_kernel
from repro.kernels.paged_attention import ops as pa_ops
from repro.kernels.paged_attention import ref as pa_ref


def _scenario(seed, B, Hq, Hkv, hd, N, P, ps, *, n_tail_unalloc=0,
              recycled=False):
    """Random pool + per-slot page tables (distinct pages, optional -1
    tails, optional stale garbage in unallocated/recycled pages)."""
    r = np.random.RandomState(seed)
    q = jnp.asarray(r.randn(B, Hq, hd), jnp.float32)
    kp = jnp.asarray(r.randn(N, ps, Hkv, hd), jnp.float32)
    vp = jnp.asarray(r.randn(N, ps, Hkv, hd), jnp.float32)
    kn = jnp.asarray(r.randn(B, Hkv, hd), jnp.float32)
    vn = jnp.asarray(r.randn(B, Hkv, hd), jnp.float32)
    pt = r.permutation(N)[:B * P].reshape(B, P).astype(np.int32)
    if n_tail_unalloc:
        pt[:, P - n_tail_unalloc:] = -1
    if recycled:
        # a freed page re-entering another slot's table mid-table: the
        # arithmetic validity mask must fence its stale rows exactly
        pt[0, 0] = pt[-1, -1] if pt[-1, -1] >= 0 else pt[0, 0]
    return q, kp, vp, kn, vn, jnp.asarray(pt)


def _compare(q, kp, vp, kn, vn, pt, pos, active, window):
    o_k, kk, vk = pa_ops.paged_decode_attention(
        q, kp, vp, kn, vn, pt, pos, window=window, active=active)
    o_r, kr, vr = pa_ref.paged_decode_attention(
        q, kp, vp, kn, vn, pt, pos,
        jnp.ones((q.shape[0],), bool) if active is None else active,
        window=window)
    # pool writes must be bitwise: verbatim row copies on both sides
    np.testing.assert_array_equal(np.asarray(kk), np.asarray(kr))
    np.testing.assert_array_equal(np.asarray(vk), np.asarray(vr))
    act = np.ones(q.shape[0], bool) if active is None else np.asarray(active)
    np.testing.assert_allclose(
        np.asarray(o_k)[act], np.asarray(o_r)[act], atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("ps", [4, 16])
@pytest.mark.parametrize("window", [0, 16])
@pytest.mark.parametrize("G", [1, 2, 4])
def test_paged_decode_kernel_matches_oracle(ps, window, G):
    Hkv = 2
    B, P, N = 3, max(1, 16 // ps), 3 * max(1, 16 // ps) + 2
    q, kp, vp, kn, vn, pt = _scenario(ps * 31 + window + G, B, G * Hkv,
                                      Hkv, 16, N, P, ps)
    cap = P * ps
    pos = jnp.asarray([0, cap // 2, cap - 1], jnp.int32)
    _compare(q, kp, vp, kn, vn, pt, pos, None, window)


@pytest.mark.parametrize("window", [0, 8])
def test_paged_decode_kernel_partial_active(window):
    B, Hkv, ps, P = 4, 2, 4, 2
    q, kp, vp, kn, vn, pt = _scenario(7 + window, B, 4, Hkv, 8, 12, P, ps)
    pos = jnp.asarray([1, 3, 5, 7], jnp.int32)
    for active in ([True, False, True, False], [False, True, True, True],
                   [True, True, True, True]):
        _compare(q, kp, vp, kn, vn, pt, pos, jnp.asarray(active), window)


def test_paged_decode_kernel_all_inactive_is_noop_write():
    """No slot writes -> pools come back bit-identical (the duplicate-
    routing fallback writes pool row (0, 0) with its own bytes)."""
    B, Hkv, ps, P = 3, 2, 4, 2
    q, kp, vp, kn, vn, pt = _scenario(11, B, 4, Hkv, 8, 8, P, ps)
    pos = jnp.asarray([2, 3, 4], jnp.int32)
    _, kk, vk = pa_ops.paged_decode_attention(
        q, kp, vp, kn, vn, pt, pos, window=0,
        active=jnp.zeros((B,), bool))
    np.testing.assert_array_equal(np.asarray(kk), np.asarray(kp))
    np.testing.assert_array_equal(np.asarray(vk), np.asarray(vp))


@pytest.mark.parametrize("ps", [4, 16])
@pytest.mark.parametrize("window", [0, 16])
def test_paged_decode_kernel_unallocated_and_recycled_pages(ps, window):
    """-1 tails and a recycled page full of stale garbage: the kernel's
    in-register validity must fence exactly what paged_slot_valid fences."""
    B, Hkv = 3, 2
    P = max(2, 32 // ps)
    N = B * P + 2
    q, kp, vp, kn, vn, pt = _scenario(ps + window, B, 4, Hkv, 16, N, P, ps,
                                      n_tail_unalloc=1, recycled=True)
    # pos inside the still-allocated prefix
    pos = jnp.asarray([0, ps - 1, (P - 1) * ps - 1], jnp.int32)
    _compare(q, kp, vp, kn, vn, pt, pos, None, window)


def _interpret_compare(q, kp, vp, kn, vn, pt, pos, active, window):
    """The kernel under the TPU interpreter (DMAs, semaphores, races) vs
    the float32 oracle on the same values: pools bitwise, live outputs
    allclose (to the output's own rounding for bfloat16), no race."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call as ipc

    o_k, kk, vk = pa_kernel.paged_decode_attention_pallas(
        q, kp, vp, kn, vn, pt, pos, active, window=window,
        interpret=pltpu.InterpretParams(detect_races=True))
    jax.block_until_ready(o_k)
    assert not ipc.races.races_found
    f32 = [x.astype(jnp.float32) for x in (q, kp, vp, kn, vn)]
    o_r, kr, vr = pa_ref.paged_decode_attention(*f32, pt, pos, active,
                                                window=window)
    np.testing.assert_array_equal(np.asarray(kk),
                                  np.asarray(kr.astype(kp.dtype)))
    np.testing.assert_array_equal(np.asarray(vk),
                                  np.asarray(vr.astype(vp.dtype)))
    act = np.asarray(active)
    tol = 1e-5 if q.dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(
        np.asarray(o_k, np.float32)[act], np.asarray(o_r)[act],
        atol=tol, rtol=tol)


# (ps, P, window, pos, active, -1 entries as (slot, page)); at these page
# sizes (two 128-wide heads) a block is K = BLOCK_BYTES // page bytes
# pages: 4 of 64 rows in bfloat16, 2 in float32, so a slot spans blocks
_WALK_CASES = {
    # the walk ends inside a block, in the first, second and last block
    "ends_mid_block": (64, 8, 0, [5, 300, 470], [1, 1, 1], []),
    # the last entry of a block, the first of the next, the table's last
    "ends_on_block_boundary": (64, 8, 0, [255, 256, 511], [1, 1, 1], []),
    # SWA rings wrapped past the window: every page live
    "swa_wrapped_all_live": (64, 8, 512, [512, 1000, 4097], [1, 1, 1], []),
    # K = 2 or 4 does not divide P = 9: the last block holds one page
    "ragged_last_block": (64, 9, 0, [575, 530, 100], [1, 1, 1], []),
    # unallocated entries inside the live range stay masked
    "unallocated_inside_live": (64, 8, 0, [300, 511, 64],
                                [1, 1, 1], [(0, 1), (1, 4), (2, 0)]),
    # inactive slots walk nothing, between and after live ones
    "mixed_inactive": (64, 8, 512, [300, 700, 20], [0, 1, 0], []),
    # no slot live: no DMA at all, pools untouched
    "all_inactive": (64, 8, 0, [300, 511, 0], [0, 0, 0], []),
    # one page per block at a large page
    "page_per_block": (256, 3, 0, [700, 256, 10], [1, 0, 1], []),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(_WALK_CASES))
def test_paged_decode_kernel_block_walk(case, dtype):
    """bfloat16 pools with paired KV heads take the packed-word read
    (heads split out of shared 32-bit words); float32 reads heads
    directly. A swapped or misread head fails the output check."""
    ps, P, window, pos, active, holes = _WALK_CASES[case]
    B, Hkv = 3, 2
    arrays = _scenario(P * 7 + ps, B, 4, Hkv, 128, B * P + 2, P, ps)
    q, kp, vp, kn, vn = (x.astype(dtype) for x in arrays[:5])
    pt = np.array(arrays[5])
    for b, j in holes:
        pt[b, j] = -1
    _interpret_compare(q, kp, vp, kn, vn, jnp.asarray(pt),
                       jnp.asarray(pos, jnp.int32),
                       jnp.asarray(active, bool), window)


@pytest.mark.parametrize("page_bytes", [512, 8192, 24576, 131072, 163840])
@pytest.mark.parametrize("P", [1, 3, 16, 256, 257])
def test_pages_per_block_rule(page_bytes, P):
    """K follows the shapes: at least one page, at most the table, and no
    more bytes than a block holds unless one page alone is larger (16
    pages at starcoder2-3b's 8 KiB pages). The blocks cover the table
    whether or not K divides it."""
    K = pa_kernel.pages_per_block(P, page_bytes)
    assert 1 <= K <= P
    assert K * page_bytes <= max(pa_kernel.BLOCK_BYTES, page_bytes)
    assert K == P or (K + 1) * page_bytes > pa_kernel.BLOCK_BYTES
    assert -(-P // K) * K >= P
    assert pa_kernel.pages_per_block(256, 16 * 2 * 128 * 2) == 16


@pytest.mark.parametrize("window", [0, 12, 16])
@pytest.mark.parametrize("ps", [1, 4, 16])
def test_live_pages_is_the_last_valid_page(window, ps):
    """The walk bound is exactly the pages up to the last entry that
    paged_slot_valid admits; numpy and jax.numpy agree."""
    P = max(1, -(-(window or 40) // ps))
    pos = np.arange(0, 3 * P * ps + 5, dtype=np.int32)
    pt = np.zeros((pos.size, P), np.int32)
    valid = np.asarray(pa_ref.slot_valid(jnp.asarray(pt), jnp.asarray(pos),
                                         ps, window))
    last = np.where(valid.any(1),
                    valid.shape[1] - 1 - np.argmax(valid[:, ::-1], 1), -1)
    want = np.minimum(last // ps + 1, P)
    act = np.ones(pos.size, bool)
    got = pa_kernel.live_pages(pos, act, ps, window, P, xp=np)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        np.asarray(pa_kernel.live_pages(jnp.asarray(pos), jnp.asarray(act),
                                        ps, window, P)), want)
    assert not pa_kernel.live_pages(pos, ~act, ps, window, P, xp=np).any()


@pytest.mark.parametrize("n_alloc", [0, 1, 3])
def test_paged_insert_matches_oracle(n_alloc):
    L, N, P, ps, Hkv, hd = 2, 9, 3, 4, 2, 16
    r = np.random.RandomState(n_alloc)
    kp = jnp.asarray(r.randn(L, N, ps, Hkv, hd), jnp.float32)
    vp = jnp.asarray(r.randn(L, N, ps, Hkv, hd), jnp.float32)
    ks = jnp.asarray(r.randn(L, P, ps, Hkv, hd), jnp.float32)
    vs = jnp.asarray(r.randn(L, P, ps, Hkv, hd), jnp.float32)
    ids = np.full(P, -1, np.int32)
    ids[:n_alloc] = r.permutation(N)[:n_alloc]
    ids = jnp.asarray(ids)
    kk, vk = pa_ops.paged_insert(kp, vp, ks, vs, ids)
    kr, vr = pa_ref.paged_insert(kp, vp, ks, vs, ids)
    np.testing.assert_array_equal(np.asarray(kk), np.asarray(kr))
    np.testing.assert_array_equal(np.asarray(vk), np.asarray(vr))


def test_attention_insert_kv_pages_kernel_path():
    """attn.insert_kv_pages(use_kernel=True) == the jnp.where path, bitwise."""
    from repro.models import attention as attn

    r = np.random.RandomState(3)
    N, ps, Hkv, hd, P = 7, 4, 2, 8, 2
    pool = attn.PagedKVPool(
        k=jnp.asarray(r.randn(N, ps, Hkv, hd), jnp.float32),
        v=jnp.asarray(r.randn(N, ps, Hkv, hd), jnp.float32))
    cap = P * ps
    one = attn.KVCache(
        k=jnp.asarray(r.randn(1, cap, Hkv, hd), jnp.float32),
        v=jnp.asarray(r.randn(1, cap, Hkv, hd), jnp.float32),
        pos=jnp.zeros((1, cap), jnp.int32))
    ids = jnp.asarray([5, 2], jnp.int32)
    ref_pool = attn.insert_kv_pages(pool, one, ids)
    ker_pool = attn.insert_kv_pages(pool, one, ids, use_kernel=True)
    np.testing.assert_array_equal(np.asarray(ker_pool.k), np.asarray(ref_pool.k))
    np.testing.assert_array_equal(np.asarray(ker_pool.v), np.asarray(ref_pool.v))


@pytest.mark.parametrize("arch", ["qwen1.5-32b", "starcoder2-3b"])
def test_paged_decode_step_kernel_vs_mask(arch):
    """Model-level: one paged_decode_step with cache_update='kernel' vs
    'mask' from the same populated cache — pool bits identical, logits
    tight-allclose, greedy argmax identical (active rows)."""
    from repro.models.model import build_model_by_name

    model = build_model_by_name(arch, reduced=True)
    params = model.init(jax.random.PRNGKey(0))
    cfg = model.config
    B, ps = 3, 4
    P = -(-(cfg.sliding_window or 16) // ps)
    n_pages = B * P + 1
    cache = model.init_paged_cache(B, n_pages, ps)
    pt = jnp.asarray(np.random.RandomState(0).permutation(n_pages)[:B * P]
                     .reshape(B, P).astype(np.int32))
    tok = jnp.asarray([1, 2, 3], jnp.int32)
    pos = jnp.asarray([0, 1, 2], jnp.int32)
    active = jnp.asarray([True, True, False])
    # populate a few rows via the mask oracle, then fork
    for t in range(2):
        _, cache = model.paged_decode_step(
            params, cache, pt, tok + t, pos + t, cache_update="mask",
            active=jnp.asarray([True, True, True]))
    lm, cm = model.paged_decode_step(params, cache, pt, tok, pos + 2,
                                     cache_update="mask", active=active)
    lk, ck = model.paged_decode_step(params, cache, pt, tok, pos + 2,
                                     cache_update="kernel", active=active)
    # layer 0 sees identical inputs -> its pool write is BITWISE; deeper
    # layers inherit the online-softmax ULP drift through the residual
    # stream, so the rest of the pool is tight-allclose instead
    np.testing.assert_array_equal(np.asarray(ck.kv.k)[0], np.asarray(cm.kv.k)[0])
    np.testing.assert_array_equal(np.asarray(ck.kv.v)[0], np.asarray(cm.kv.v)[0])
    np.testing.assert_allclose(np.asarray(ck.kv.k), np.asarray(cm.kv.k),
                               atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(ck.kv.v), np.asarray(cm.kv.v),
                               atol=1e-5, rtol=1e-4)
    act = np.asarray(active)
    np.testing.assert_allclose(np.asarray(lk)[act], np.asarray(lm)[act],
                               atol=2e-4, rtol=2e-4)
    assert (np.asarray(lk).argmax(-1)[act] ==
            np.asarray(lm).argmax(-1)[act]).all()


def test_insert_cache_pages_kernel_vs_mask():
    from repro.models.model import build_model_by_name
    from repro.models.transformer import insert_cache_pages

    model = build_model_by_name("qwen1.5-32b", reduced=True)
    params = model.init(jax.random.PRNGKey(0))
    B, ps, P = 2, 4, 3
    cache = model.init_paged_cache(B, B * P, ps)
    # a real batch-1 prefill cache, padded to the page multiple
    toks = jnp.ones((1, 8), jnp.int32)
    _, one = model.prefill(params, {"tokens": toks}, pad_to=P * ps)
    ids = jnp.asarray([4, 1, -1], jnp.int32)
    cm = insert_cache_pages(cache, one, jnp.int32(0), ids)
    ck = insert_cache_pages(cache, one, jnp.int32(0), ids,
                            cache_update="kernel")
    np.testing.assert_array_equal(np.asarray(ck.kv.k), np.asarray(cm.kv.k))
    np.testing.assert_array_equal(np.asarray(ck.kv.v), np.asarray(cm.kv.v))


def test_paged_serve_loop_kernel_stream_parity():
    """Greedy streams through PagedServeLoop: cache_update='kernel' must be
    bit-identical to 'mask' (the tentpole exit bar)."""
    from repro.models.model import build_model_by_name
    from repro.serve import PagedServeLoop, poisson_trace

    model = build_model_by_name("qwen1.5-32b", reduced=True)
    params = model.init(jax.random.PRNGKey(0))
    trace = poisson_trace(6, rate=4.0, plen_choices=(8, 12),
                          max_new_choices=(6, 10),
                          vocab_size=model.config.vocab_size, seed=0)
    outs = {}
    for cu in ("mask", "kernel"):
        reqs = [r.clone() for r in trace]
        PagedServeLoop(model, params, n_slots=3, capacity=32, page_size=8,
                       n_pages=12, cache_update=cu).run(reqs)
        outs[cu] = [r.out for r in reqs]
    assert outs["kernel"] == outs["mask"]


@pytest.mark.slow
def test_paged_serve_loop_kernel_stream_parity_swa():
    """Same bar on a sliding-window arch (ring-slot validity in-kernel)."""
    from repro.models.model import build_model_by_name
    from repro.serve import PagedServeLoop, poisson_trace

    model = build_model_by_name("starcoder2-3b", reduced=True)
    params = model.init(jax.random.PRNGKey(0))
    trace = poisson_trace(6, rate=4.0, plen_choices=(8, 16),
                          max_new_choices=(6, 10),
                          vocab_size=model.config.vocab_size, seed=1)
    outs = {}
    for cu in ("mask", "kernel"):
        reqs = [r.clone() for r in trace]
        PagedServeLoop(model, params, n_slots=3, capacity=32, page_size=8,
                       cache_update=cu).run(reqs)
        outs[cu] = [r.out for r in reqs]
    assert outs["kernel"] == outs["mask"]


def test_auto_interpret_env_override(monkeypatch):
    """Interpret mode is on exactly when the backend is the CPU; the old
    REPRO_PALLAS_INTERPRET environment override is gone, so it cannot put
    a kernel into the emulator on a TPU."""
    from repro import kernels

    assert kernels.auto_interpret() == (jax.default_backend() == "cpu")
    for backend, want in (("cpu", True), ("tpu", False), ("gpu", False)):
        monkeypatch.setattr(kernels.jax, "default_backend", lambda b=backend: b)
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1" if backend == "tpu" else "0")
        assert kernels.auto_interpret() is want


@pytest.mark.skipif(
    jax.default_backend() not in ("tpu", "gpu"),
    reason="compile-path (non-interpret) Pallas needs an accelerator "
    "backend; CPU runs the interpret-mode suite above",
)
def test_paged_decode_kernel_compile_path():
    """Natively-compiled paged decode == the jnp oracle on accelerators."""
    B, Hkv, ps, P, N = 2, 2, 16, 2, 6
    q, kp, vp, kn, vn, pt = _scenario(0, B, 8, Hkv, 64, N, P, ps)
    pos = jnp.asarray([5, 20], jnp.int32)
    act = jnp.ones((B,), bool)
    o_k, kk, vk = pa_ops.paged_decode_attention(
        q, kp, vp, kn, vn, pt, pos, window=0, active=act, interpret=False)
    o_r, kr, vr = pa_ref.paged_decode_attention(
        q, kp, vp, kn, vn, pt, pos, act, window=0)
    np.testing.assert_array_equal(np.asarray(kk), np.asarray(kr))
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                               atol=1e-5, rtol=1e-5)
