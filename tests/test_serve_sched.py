"""Front-end scheduler on the paged pool (DESIGN.md §12.2).

Pins the PR's contracts: greedy token streams bit-identical to the
SerialLoop oracle with prefix caching enabled, for multiple prefill
chunk widths, and under FORCED slot preemption (pool sized so the trace
cannot complete without evictions) — for full-attention, SWA-ring and
hybrid-SSM families on the preemption path; page-refcount conservation
under admit/preempt/retire churn; deterministic bursty/shared-prefix
traces that keep the legacy RNG stream bit-identical at default args;
the seedless percentile helpers; and the chunk-prefill launch bundle.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.metrics.logger import latency_summary, percentile
from repro.models.model import build_model_by_name
from repro.serve import (
    PageAllocator,
    PagedServeLoop,
    PrefixCache,
    SamplerConfig,
    SerialLoop,
    ServeUnsupportedError,
    poisson_trace,
)


def _clone(reqs):
    return [r.clone() for r in reqs]


@pytest.fixture(scope="module")
def qwen():
    model = build_model_by_name("qwen1.5-32b", reduced=True)
    return model, model.init(jax.random.PRNGKey(0))


def _family_trace(model, n=6, seed=1, max_new=(2, 4, 6)):
    """Shared-prefix families (16 tokens = 2 pages at page_size 8) so the
    prefix cache actually hits."""
    return poisson_trace(
        n, rate=1.0, plen_choices=(3, 5, 9), max_new_choices=max_new,
        vocab_size=model.config.vocab_size, seed=seed,
        prefix_families=2, prefix_len=16)


def _oracle(model, params, trace, capacity=32, sampler=None):
    a = _clone(trace)
    SerialLoop(model, params, capacity=capacity, sampler=sampler).run(a)
    return [r.out for r in a]


# ---------------------------------------------------------------------------
# parity: every scheduler feature must keep greedy streams bit-identical
# ---------------------------------------------------------------------------


def test_prefix_cache_parity_and_prefill_economy(qwen):
    """Prefix caching changes WHAT is prefilled (suffixes only), never
    what is generated; shared pages must actually be hit."""
    model, params = qwen
    trace = _family_trace(model)
    want = _oracle(model, params, trace)

    loop = PagedServeLoop(model, params, n_slots=3, capacity=32,
                          page_size=8, bucket=8, prefix_cache=True)
    reqs = _clone(trace)
    stats = loop.run(reqs)
    assert [r.out for r in reqs] == want
    assert stats["prefix_hit_tokens"] > 0, "trace never hit the cache"
    assert stats["prefilled_tokens"] < sum(r.plen for r in trace), \
        "prefix hits did not reduce prefilled tokens"
    loop.check_invariants()


@pytest.mark.parametrize("chunk", [4, 16])
@pytest.mark.parametrize("prefix", [False, True])
def test_chunked_prefill_parity(qwen, chunk, prefix):
    """Chunk width is a scheduling knob: two widths, with and without
    prefix seeding, all bit-identical to the serial oracle."""
    model, params = qwen
    trace = _family_trace(model, seed=2)
    want = _oracle(model, params, trace)

    loop = PagedServeLoop(model, params, n_slots=3, capacity=32,
                          page_size=8, bucket=8, prefix_cache=prefix,
                          prefill_chunk=chunk)
    reqs = _clone(trace)
    stats = loop.run(reqs)
    assert [r.out for r in reqs] == want
    assert stats["extend_dispatches"] > 0
    loop.check_invariants()


def test_forced_preemption_parity(qwen):
    """Pool sized so the trace CANNOT complete without evicting a live
    request; streams still match the oracle token for token."""
    model, params = qwen
    trace = _family_trace(model, seed=3, max_new=(4, 8))
    want = _oracle(model, params, trace)

    # each request needs ceil((16+9+8-1)/8) <= 4 pages; 6 pages means a
    # third concurrent request only ever enters by preempting
    loop = PagedServeLoop(model, params, n_slots=3, capacity=32,
                          page_size=8, bucket=8, n_pages=6,
                          preempt=True, preempt_after=1)
    reqs = _clone(trace)
    stats = loop.run(reqs)
    assert [r.out for r in reqs] == want
    assert stats["preemptions"] >= 1, "pool was generous enough to avoid it"
    assert stats["restore_dispatches"] == stats["preemptions"]
    loop.check_invariants()


def test_all_features_parity_sampled(qwen):
    """Scheduling cannot touch sampled streams either: per-request
    fold_in(rid)/fold_in(nstep) draws are batch- and schedule-independent,
    so prefix+chunk+preempt under a starved pool still reproduces the
    serial sampled trace bit for bit."""
    model, params = qwen
    sampler = SamplerConfig(temperature=0.7, top_k=8, seed=5)
    trace = _family_trace(model, seed=4, max_new=(3, 5))
    want = _oracle(model, params, trace, sampler=sampler)

    loop = PagedServeLoop(model, params, n_slots=3, capacity=32,
                          page_size=8, bucket=8, n_pages=8,
                          sampler=sampler, prefix_cache=True,
                          prefill_chunk=4, preempt=True, preempt_after=1)
    reqs = _clone(trace)
    loop.run(reqs)
    assert [r.out for r in reqs] == want
    loop.check_invariants()


@pytest.mark.parametrize("arch", ["starcoder2-3b", "hymba-1.5b"])
def test_preemption_parity_swa_and_hybrid(arch):
    """Preemption works for EVERY paged family: SWA ring pages stage and
    restore verbatim, hybrid models carry their SSM row alongside."""
    model = build_model_by_name(arch, reduced=True)
    params = model.init(jax.random.PRNGKey(0))
    trace = poisson_trace(5, rate=5.0, plen_choices=(5, 9, 12),
                          max_new_choices=(4, 6),
                          vocab_size=model.config.vocab_size, seed=2)
    want = _oracle(model, params, trace)

    # pool = largest single request + one page: two sizable requests can
    # never co-reside, so the burst of arrivals can only drain by evicting
    probe = PagedServeLoop(model, params, n_slots=3, capacity=32,
                           page_size=8, bucket=8)
    n_pages = max(probe.allocator.pages_for(probe._rows_needed(r))
                  for r in trace) + 1
    loop = PagedServeLoop(model, params, n_slots=3, capacity=32,
                          page_size=8, bucket=8, n_pages=n_pages,
                          preempt=True, preempt_after=1)
    reqs = _clone(trace)
    stats = loop.run(reqs)
    assert [r.out for r in reqs] == want
    assert stats["preemptions"] >= 1
    loop.check_invariants()


def test_extend_gates(qwen):
    """Prefix caching / chunked prefill refuse non-full-attention
    configs loudly; bad chunk widths refuse too."""
    model, params = qwen
    swa = build_model_by_name("starcoder2-3b", reduced=True)
    with pytest.raises(ServeUnsupportedError, match="full-attention"):
        PagedServeLoop(swa, None, prefix_cache=True)
    with pytest.raises(ValueError, match="prefill_chunk"):
        PagedServeLoop(model, params, prefill_chunk=0)
    # preemption alone stays available for SWA (verbatim page staging)
    PagedServeLoop(swa, None, preempt=True)


# ---------------------------------------------------------------------------
# refcount conservation (PageAllocator + PrefixCache)
# ---------------------------------------------------------------------------


def test_allocator_refcount_lifecycle():
    a = PageAllocator(8, 4)
    ids = a.alloc(2)
    assert [a.refcount(i) for i in ids] == [1, 1]
    a.share(ids)  # second owner (e.g. the prefix cache)
    assert [a.refcount(i) for i in ids] == [2, 2]
    a.free(ids)  # first owner gone: pages stay in use
    assert a.free_pages == 6 and [a.refcount(i) for i in ids] == [1, 1]
    a.free(ids)  # last owner gone: pages return to the free list
    assert a.free_pages == 8 and a.refcount(int(ids[0])) == 0
    with pytest.raises(AssertionError, match="double free"):
        a.free([int(ids[0])])
    with pytest.raises(AssertionError, match="share of free page"):
        a.share([int(ids[0])])
    a.check()


def test_allocator_refcount_conservation_check():
    """check(page_tables=, cached_pages=) cross-validates the ledger
    against who actually references each page."""
    a = PageAllocator(8, 4)
    row0 = np.array([0, 1, -1], np.int32)
    got = a.alloc(2)
    assert list(got) == [0, 1]
    a.share([0])  # page 0 aliased into a second row
    row1 = np.array([0, -1, -1], np.int32)
    a.check(page_tables=[row0, row1], cached_pages=None)
    # a reference the tables don't explain -> conservation violation
    a._refs[1] += 1
    with pytest.raises(AssertionError, match="refcount"):
        a.check(page_tables=[row0, row1], cached_pages=None)
    a._refs[1] -= 1
    # a page the ledger says is in use but nobody references -> leak
    with pytest.raises(AssertionError, match="unreferenced"):
        a.check(page_tables=[row1], cached_pages=None)


def test_prefix_cache_register_lookup_evict():
    a = PageAllocator(8, 4)
    pc = PrefixCache(a)
    toks = np.arange(11, dtype=np.int32)  # 2 full pages + 3 tail tokens
    row = a.alloc(3)
    pc.register(toks, row, plen=11)  # publishes pages 0..1 (11 // 4 = 2)
    assert len(pc) == 2 and a.refcount(int(row[0])) == 2
    # longest-run lookup; a full-prompt hit is capped so >=1 token prefills
    assert pc.lookup(toks) == [int(row[0]), int(row[1])]
    assert pc.lookup(toks[:8]) == [int(row[0])]  # (8-1)//4 = 1 page max
    other = np.concatenate([toks[:4], [99, 98, 97, 96]]).astype(np.int32)
    assert pc.lookup(other) == [int(row[0])]  # shared first page only
    a.check(page_tables=[row], cached_pages=pc.pages)
    # owner retires: cached pages survive on the cache's reference
    a.free(row)
    a.check(page_tables=[], cached_pages=pc.pages)
    assert a.free_pages == 6
    # eviction only releases cache-only pages, LRU first
    assert pc.evict_for(5) == 2 and len(pc) == 0 and a.free_pages == 8
    a.check()


class _CheckedLoop(PagedServeLoop):
    """Audits refcount conservation after EVERY tick."""

    def tick(self, queue=None):
        super().tick(queue)
        self.check_invariants()


def test_refcount_churn_under_admit_preempt_retire(qwen):
    """The full scheduler on a starved pool: admissions, prefix shares,
    preemptions, restores and retirements interleave, and the refcount
    ledger must balance after every single tick."""
    model, params = qwen
    trace = _family_trace(model, n=8, seed=6, max_new=(2, 4, 8))
    want = _oracle(model, params, trace)

    loop = _CheckedLoop(model, params, n_slots=3, capacity=32,
                        page_size=8, bucket=8, n_pages=9,
                        prefix_cache=True, prefill_chunk=4,
                        preempt=True, preempt_after=1)
    reqs = _clone(trace)
    stats = loop.run(reqs)
    assert [r.out for r in reqs] == want
    assert stats["preemptions"] >= 1 and stats["prefix_hit_tokens"] > 0
    # after drain only the cache holds pages: every in-use page refcount 1
    loop.check_invariants()
    assert loop.allocator.pages_in_use == len(loop.prefix.pages)


# ---------------------------------------------------------------------------
# trace generator: bursty overload + shared-prefix families
# ---------------------------------------------------------------------------


def test_trace_default_args_reproduce_legacy_stream():
    """The new knobs must not perturb the RNG stream at default values:
    seeds pinned by older tests/benchmarks stay bit-identical."""
    def legacy(n, rate, plens, max_news, vocab, seed):
        r = np.random.RandomState(seed)
        gaps = r.exponential(1.0 / max(rate, 1e-9), n)
        arrivals = np.floor(np.cumsum(gaps)).astype(int)
        out = []
        for i in range(n):
            plen = int(r.choice(plens))
            toks = r.randint(0, vocab, plen).astype(np.int32)
            out.append((int(arrivals[i]), toks, int(r.choice(max_news))))
        return out

    got = poisson_trace(12, rate=1.5, plen_choices=(4, 8),
                        max_new_choices=(2, 6), vocab_size=97, seed=42)
    want = legacy(12, 1.5, (4, 8), (2, 6), 97, 42)
    for g, (arr, toks, mn) in zip(got, want):
        assert (g.arrival, g.max_new) == (arr, mn)
        np.testing.assert_array_equal(g.tokens, toks)


def test_trace_burst_and_families_deterministic():
    kw = dict(rate=1.0, plen_choices=(4, 8), max_new_choices=(2,),
              vocab_size=64, seed=7, burst_mult=3.0, burst_period=4,
              prefix_families=2, prefix_len=16)
    a, b = poisson_trace(16, **kw), poisson_trace(16, **kw)
    for ra, rb in zip(a, b):
        assert ra.arrival == rb.arrival and ra.max_new == rb.max_new
        np.testing.assert_array_equal(ra.tokens, rb.tokens)
    # families: every prompt starts with one of exactly two 16-token
    # prefixes; suffix lengths come from plen_choices
    heads = {r.tokens[:16].tobytes() for r in a}
    assert len(heads) == 2
    assert {r.plen - 16 for r in a} <= {4, 8}
    # bursts COMPRESS arrivals (same gaps, some divided by burst_mult)
    calm = poisson_trace(16, **{**kw, "burst_mult": 1.0})
    assert a[-1].arrival <= calm[-1].arrival
    assert any(ra.arrival != rc.arrival for ra, rc in zip(a, calm))


# ---------------------------------------------------------------------------
# percentile helpers (metrics/logger.py)
# ---------------------------------------------------------------------------


def test_percentile_helpers():
    vals = list(range(1, 101))
    assert percentile(vals, 50) == 50.5
    assert percentile(vals, 99) == pytest.approx(99.01)
    assert percentile([3.0], 99) == 3.0
    assert np.isnan(percentile([], 50))
    s = latency_summary([1.0, 2.0, 3.0, 4.0], prefix="ttft_")
    assert s["ttft_n"] == 4 and s["ttft_mean"] == 2.5
    assert s["ttft_p50"] == 2.5 and s["ttft_p99"] == pytest.approx(3.97)
    empty = latency_summary([])
    assert empty["n"] == 0 and np.isnan(empty["p99"])


# ---------------------------------------------------------------------------
# chunk-prefill launch bundle (train/steps.py)
# ---------------------------------------------------------------------------


def test_paged_prefill_bundle(qwen):
    from jax.sharding import Mesh
    from repro.configs.base import ShapeConfig
    from repro.train.steps import build_bundle

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    model, params = qwen
    shape = ShapeConfig("serve", 32, 4, "prefill")
    b = build_bundle(model, mesh, shape, kind="prefill", paged=True,
                     page_size=8, chunk=8)
    assert b.name == "prefill_chunk[paged]"
    structs = b.make_inputs()
    assert structs[3].shape == (1, 8)  # one chunk of `chunk` tokens
    n_pages = structs[1].kv.k.shape[1]
    cache = model.init_paged_cache(4, n_pages, 8)
    row = np.array([2, 5, -1, -1], np.int32)  # 2 allocated pages
    toks = jnp.arange(1, 9, dtype=jnp.int32)[None]
    logits, new_cache = b.fn(params, cache, jnp.asarray(row), toks,
                             jnp.int32(0), jnp.int32(6))
    assert logits.shape == (1, model.config.vocab_size)
    k = np.asarray(new_cache.kv.k)  # [L, n_pages, ps, Hkv, hd]
    assert (k[:, 2, :6] != 0).any()  # rows 0..5 -> page row[0]=2
    assert (k[:, 2, 6:] == 0).all()  # padded rows masked out
    assert (k[:, 5] == 0).all()      # page 5 holds rows 8.. (untouched)
    others = [i for i in range(n_pages) if i not in (2, 5)]
    assert (k[:, others] == 0).all()


# ---------------------------------------------------------------------------
# example CLI (subprocess; the features are pinned in-process above)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_serve_example_scheduler_flags_parity():
    """examples/serve_decode.py threads --prefix-cache/--prefill-chunk/
    --preempt into PagedServeLoop and --check still passes."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "examples/serve_decode.py", "--reduced",
         "--arch", "qwen1.5-32b", "--paged", "--prefix-cache", "--prefill-chunk", "4", "--preempt",
         "--slots", "3", "--capacity", "64", "--page-size", "8",
         "--requests", "6", "--max-new", "8", "--check"],
        capture_output=True, text=True, env=env, cwd=root, timeout=600,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "PARITY OK" in r.stdout


# ---------------------------------------------------------------------------
# kernel->mask chunk-prefill lowering: loud, structured, once
# ---------------------------------------------------------------------------


def test_kernel_extend_fallback_warns_once(qwen, monkeypatch):
    """`cache_update="kernel"` has no chunk-prefill variant yet (the open
    §12.2 follow-up: a kernel extend path) — the lowering to the mask
    path must announce itself ONCE per process via the structured
    KernelExtendFallbackWarning, not silently."""
    import warnings

    from repro.models import transformer

    model, params = qwen
    monkeypatch.setattr(transformer, "_KERNEL_EXTEND_WARNED", False)

    def build():
        return PagedServeLoop(model, params, n_slots=3, capacity=32,
                              page_size=8, bucket=8, prefill_chunk=8,
                              cache_update="kernel")

    with pytest.warns(transformer.KernelExtendFallbackWarning,
                      match="§12.2"):
        build()
    with warnings.catch_warnings():  # second build: already warned
        warnings.simplefilter("error",
                              transformer.KernelExtendFallbackWarning)
        build()
