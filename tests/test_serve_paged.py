"""Paged-KV serve loop (DESIGN.md §12): pooled page capacity + sampling.

Pins the paged contracts: greedy token streams bit-identical to the
contiguous ServeLoop AND the SerialLoop oracle (dense, SWA-ring and
hybrid families; MoE when expert capacity doesn't bind), page-reuse can
never poison a new request (adversarial retire/readmit into the same
pages), allocator free-list invariants, admission backpressure (queue,
don't crash) on pool exhaustion, graceful rejection of impossible
demands, and the sampled-decode contracts — ``temperature=0`` ==
greedy bitwise, ``top_k=1`` == greedy, and per-request sample streams
independent of batch composition.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models.model import build_model, build_model_by_name
from repro.serve import (
    PageAllocator,
    PagedServeLoop,
    Request,
    SamplerConfig,
    SerialLoop,
    ServeLoop,
    ServeUnsupportedError,
    poisson_trace,
)


def _clone(reqs):
    return [r.clone() for r in reqs]


def _trace(model, n=6, seed=1):
    return poisson_trace(
        n, rate=1.0, plen_choices=(5, 9, 12, 16),
        max_new_choices=(2, 4, 6), vocab_size=model.config.vocab_size,
        seed=seed,
    )


def _build(name):
    model = build_model_by_name(name, reduced=True)
    return model, model.init(jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# parity: paged == contiguous == serial, token for token
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["starcoder2-3b", "qwen1.5-32b", "hymba-1.5b"])
def test_paged_token_parity(arch):
    """Greedy streams from the paged loop are bit-identical per request to
    both the contiguous loop and the serial oracle: SWA ring pages
    (starcoder2), full-attention pooled pages (qwen) and the hybrid
    family's dense per-slot SSM rows riding beside paged KV (hymba).
    n_slots < n_requests forces retirement + page reuse mid-trace."""
    model, params = _build(arch)
    reqs = _trace(model)
    a, b, c = _clone(reqs), _clone(reqs), _clone(reqs)
    stats = PagedServeLoop(model, params, n_slots=3, capacity=32,
                           page_size=8, bucket=8).run(a)
    ServeLoop(model, params, n_slots=3, capacity=32, bucket=8).run(b)
    SerialLoop(model, params).run(c)
    for qa, qb, qc in zip(a, b, c):
        assert qa.out == qc.out, f"request {qa.rid}: paged != serial"
        assert qb.out == qc.out, f"request {qb.rid}: contiguous != serial"
    assert stats["failed"] == 0
    # pooled pages: the peak demand stayed below the worst-case reservation
    assert stats["peak_pages"] <= stats["n_pages"]


def test_paged_moe_parity_when_capacity_never_binds():
    """MoE under paged KV inherits the contiguous loop's caveat: only
    static expert-capacity overflow may diverge — with capacity unbound
    the paged stream matches the serial oracle bitwise."""
    cfg = dataclasses.replace(get_arch("qwen2-moe-a2.7b").reduced(),
                              capacity_factor=100.0)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    reqs = _trace(model, n=5)
    a, b = _clone(reqs), _clone(reqs)
    PagedServeLoop(model, params, n_slots=3, capacity=32, page_size=8,
                   bucket=8).run(a)
    SerialLoop(model, params).run(b)
    assert [q.out for q in a] == [q.out for q in b]


def test_paged_swa_ring_wrap_parity():
    """A prompt longer than the sliding window wraps the paged ring (all
    ring pages in play, slot = pos % W) and still matches the serial
    stream token for token."""
    model, params = _build("starcoder2-3b")
    cfg = model.config
    W = cfg.sliding_window
    r = np.random.RandomState(7)
    reqs = [Request(rid=0, tokens=r.randint(0, cfg.vocab_size, W + 6),
                    max_new=5, arrival=0),
            Request(rid=1, tokens=r.randint(0, cfg.vocab_size, 9),
                    max_new=4, arrival=0)]
    a, b = _clone(reqs), _clone(reqs)
    PagedServeLoop(model, params, n_slots=2, page_size=16).run(a)
    SerialLoop(model, params).run(b)
    assert [q.out for q in a] == [q.out for q in b]


def test_recurrent_family_refused():
    """xLSTM keeps O(1) recurrent state per slot — nothing to page; the
    paged loop must refuse with a clear reason, not crash."""
    model = build_model_by_name("xlstm-1.3b", reduced=True)
    with pytest.raises(ServeUnsupportedError, match="page"):
        PagedServeLoop(model, params=None)


# ---------------------------------------------------------------------------
# page reuse can never poison a new request
# ---------------------------------------------------------------------------


class _RecordingLoop(PagedServeLoop):
    """Logs every admission's (rid, page-table row) for reuse assertions."""

    alloc_log: list

    def _insert_request(self, slot, req, one):
        super()._insert_request(slot, req, one)
        self.alloc_log.append((req.rid, self.page_table[slot].copy()))


def test_page_reuse_does_not_poison_new_requests():
    """Adversarial reuse: a tight pool forces every late request into
    pages freed by earlier retirements. The recycled-page streams must be
    bitwise identical to a fresh-cache serial run of each request — the
    full-page overwrite at insert plus the arithmetic validity mask make
    stale KV unreachable."""
    model, params = _build("qwen1.5-32b")
    reqs = _trace(model, n=8, seed=9)
    for q in reqs:
        q.arrival = 0  # maximum admission pressure
    # pool sized to ~2 live requests: retirement must recycle pages
    loop = _RecordingLoop(model, params, n_slots=2, capacity=32, page_size=8,
                          n_pages=6, bucket=8)
    loop.alloc_log = []
    a = _clone(reqs)
    stats = loop.run(a)
    assert stats["failed"] == 0
    loop.allocator.check()
    assert loop.allocator.pages_in_use == 0  # every page returned

    # reuse actually happened: some page id served two different requests
    owners = {}
    reused = 0
    for rid, row in loop.alloc_log:
        for pid in row[row >= 0]:
            reused += owners.get(int(pid), rid) != rid
            owners[int(pid)] = rid
    assert reused > 0, "trace never recycled a page — test is vacuous"

    b = _clone(reqs)
    SerialLoop(model, params).run(b)
    for qa, qb in zip(a, b):
        assert qa.out == qb.out, f"request {qa.rid} poisoned by page reuse"


def test_allocator_free_list_invariants():
    """Unit-granular pages can't fragment; what CAN break is conservation
    / disjointness / double alloc-free — check() after a churn storm."""
    al = PageAllocator(8, page_size=4)
    assert al.pages_for(1) == 1 and al.pages_for(4) == 1
    assert al.pages_for(5) == 2 and al.pages_for(0) == 0
    a = al.alloc(3)
    b = al.alloc(5)
    assert al.alloc(1) is None  # exhausted -> backpressure, not a crash
    al.check()
    al.free(a)
    assert al.free_pages == 3 and al.pages_in_use == 5
    c = al.alloc(2)
    al.check()
    assert not set(map(int, c)) & set(map(int, b))  # disjoint live sets
    with pytest.raises(AssertionError, match="double free"):
        al.free(a[:1])  # a was already freed
    al.free(b)
    al.free(c)
    al.check()
    assert al.free_pages == 8 and al.peak_in_use == 8

    rng = np.random.RandomState(0)
    live = []
    for _ in range(200):  # random churn keeps every invariant
        if live and rng.rand() < 0.5:
            al.free(live.pop(rng.randint(len(live))))
        else:
            got = al.alloc(rng.randint(1, 4))
            if got is not None:
                live.append(got)
        al.check()


# ---------------------------------------------------------------------------
# admission backpressure / graceful rejection
# ---------------------------------------------------------------------------


def test_pool_exhaustion_queues_instead_of_crashing():
    """More live demand than the pool: admission WAITS (FIFO) until
    retirement frees pages — every request completes, streams still match
    the serial oracle, and concurrency provably stayed pool-bound."""
    model, params = _build("qwen1.5-32b")
    cfg = model.config
    r = np.random.RandomState(8)
    reqs = [Request(rid=i, tokens=r.randint(0, cfg.vocab_size, 9),
                    max_new=4, arrival=0) for i in range(4)]
    # 2 pages of 8 rows: exactly ONE request (12 rows) fits at a time
    loop = PagedServeLoop(model, params, n_slots=4, capacity=32, page_size=8,
                          n_pages=2, bucket=8)
    a = _clone(reqs)
    stats = loop.run(a)
    assert stats["failed"] == 0
    assert stats["peak_pages"] <= 2  # never over-admitted
    b = _clone(reqs)
    SerialLoop(model, params).run(b)
    assert [q.out for q in a] == [q.out for q in b]


def test_impossible_pool_demand_rejected_gracefully():
    """A request whose page demand exceeds the WHOLE pool can never be
    admitted — it must fail on the Request (like the contiguous oversized
    case) while the rest of the trace keeps serving."""
    model, params = _build("qwen1.5-32b")
    cfg = model.config
    r = np.random.RandomState(8)
    good = Request(rid=0, tokens=r.randint(0, cfg.vocab_size, 9), max_new=4,
                   arrival=0)
    big = Request(rid=1, tokens=r.randint(0, cfg.vocab_size, 20), max_new=6,
                  arrival=0)  # 25 rows = 4 pages > 2-page pool
    loop = PagedServeLoop(model, params, n_slots=2, capacity=32, page_size=8,
                          n_pages=2, bucket=8)
    served = [good.clone(), big.clone()]
    stats = loop.run(served)
    assert stats["failed"] == 1 and stats["failed_rids"] == [1]
    assert "pool" in served[1].failed and served[1].out == []
    ref = [good.clone()]
    SerialLoop(model, params).run(ref)
    assert served[0].out == ref[0].out


def test_paged_parity_survives_scatter_cache_update():
    """The scatter pool write (pool.at[phys, row] with dropped OOB rows)
    matches the masked path and the serial oracle bit for bit."""
    model, params = _build("qwen1.5-32b")
    reqs = _trace(model, n=4)
    a, b = _clone(reqs), _clone(reqs)
    PagedServeLoop(model, params, n_slots=2, capacity=32, page_size=8,
                   bucket=8, cache_update="scatter").run(a)
    SerialLoop(model, params, cache_update="scatter").run(b)
    assert [q.out for q in a] == [q.out for q in b]


# ---------------------------------------------------------------------------
# sampled decode
# ---------------------------------------------------------------------------


def test_sampler_validation_and_topk_clamp():
    """Bad knobs fail at config time, not as an opaque lax error inside
    the first jitted dispatch; top_k > vocab means 'keep everything'."""
    from repro.serve.sampling import make_sample_fn

    with pytest.raises(ValueError, match="top_k"):
        make_sample_fn(SamplerConfig(temperature=1.0, top_k=-1))
    with pytest.raises(ValueError, match="temperature"):
        make_sample_fn(SamplerConfig(temperature=-0.5))
    logits = jnp.asarray(np.random.RandomState(0).randn(3, 16), jnp.float32)
    rid = jnp.arange(3, dtype=jnp.int32)
    ns = jnp.zeros(3, jnp.int32)
    full = make_sample_fn(SamplerConfig(temperature=1.0, seed=1))(
        logits, rid, ns)
    huge = make_sample_fn(SamplerConfig(temperature=1.0, top_k=10**6,
                                        seed=1))(logits, rid, ns)
    np.testing.assert_array_equal(np.asarray(full), np.asarray(huge))


def test_temperature0_and_topk1_are_greedy_bitwise():
    """The temperature=0 sampler IS the greedy argmax program (identical
    streams, bit for bit); top_k=1 collapses the categorical onto the
    argmax token too (ties are measure-zero with real weights)."""
    model, params = _build("qwen1.5-32b")
    reqs = _trace(model, n=4)
    greedy, t0, k1 = _clone(reqs), _clone(reqs), _clone(reqs)
    kw = dict(n_slots=2, capacity=32, page_size=8, bucket=8)
    PagedServeLoop(model, params, **kw).run(greedy)
    PagedServeLoop(model, params, sampler=SamplerConfig(temperature=0.0),
                   **kw).run(t0)
    PagedServeLoop(model, params,
                   sampler=SamplerConfig(temperature=0.7, top_k=1, seed=5),
                   **kw).run(k1)
    assert [q.out for q in t0] == [q.out for q in greedy]
    assert [q.out for q in k1] == [q.out for q in greedy]


def test_sample_streams_independent_of_batch_composition():
    """fold_in(rid)/fold_in(nstep) keying: a request draws the SAME
    sampled stream whether it shares the batch with 2 neighbors, runs
    alone (n_slots=1), or goes through the serial loop — and sampling
    actually deviates from greedy somewhere (non-vacuous)."""
    model, params = _build("qwen1.5-32b")
    reqs = _trace(model, n=5, seed=2)
    smp = SamplerConfig(temperature=0.8, top_k=8, seed=3)
    batched, alone, serial, greedy = (_clone(reqs) for _ in range(4))
    PagedServeLoop(model, params, n_slots=3, capacity=32, page_size=8,
                   bucket=8, sampler=smp).run(batched)
    PagedServeLoop(model, params, n_slots=1, capacity=32, page_size=8,
                   bucket=8, sampler=smp).run(alone)
    SerialLoop(model, params, sampler=smp).run(serial)
    ServeLoop(model, params, n_slots=3, capacity=32, bucket=8).run(greedy)
    assert [q.out for q in batched] == [q.out for q in alone]
    assert [q.out for q in batched] == [q.out for q in serial]
    assert any(a.out != g.out for a, g in zip(batched, greedy))


# ---------------------------------------------------------------------------
# launch-path seam (train/steps.py)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch, walked", [("qwen1.5-32b", 15),
                                           ("starcoder2-3b", 47)])
def test_kv_page_counters_match_hand_count(arch, walked):
    """Per decode dispatch, kv_pages_walked adds the pages the decode
    kernel's walk bound reads for each live slot and kv_pages_listed the
    slots' whole tables. Hand count at page size 4: a request's decode
    dispatches sit at positions plen .. plen + len(out) - 2 and read
    ceil((min(pos, W - 1) + 1) / 4) pages: A (plen 5) 2+2+2, B (plen 9)
    3+3, C (plen 3, arriving later, into a freed slot) 1+2; starcoder2's
    ring (W 64, 16 pages) adds D (plen 66, past the window) 16+16."""
    model, params = _build(arch)
    reqs = [Request(rid=0, tokens=np.arange(5) % 7, max_new=4),
            Request(rid=1, tokens=np.arange(9) % 7, max_new=3),
            Request(rid=2, tokens=np.arange(3) % 7, max_new=3, arrival=1)]
    if model.config.sliding_window:
        reqs.append(Request(rid=3, tokens=np.arange(66) % 7, max_new=3,
                            arrival=1))
    loop = PagedServeLoop(model, params, n_slots=2, capacity=16,
                          page_size=4, cache_update="kernel")
    stats = loop.run(reqs)
    assert all(len(r.out) == r.max_new for r in reqs)
    assert stats["kv_pages_walked"] == walked
    assert stats["kv_pages_listed"] == \
        stats["decode_dispatches"] * 2 * loop.pages_per_slot


def test_paged_decode_bundle():
    from jax.sharding import Mesh
    from repro.configs.base import ShapeConfig
    from repro.train.steps import build_bundle

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    model = build_model_by_name("qwen1.5-32b", reduced=True)
    shape = ShapeConfig("serve", 32, 4, "decode")
    b = build_bundle(model, mesh, shape, kind="decode", paged=True,
                     page_size=8)
    assert b.name == "decode_step[paged]"
    params = model.init(jax.random.PRNGKey(0))
    structs = b.make_inputs()
    n_pages = structs[1].kv.k.shape[1]
    assert n_pages == 4 * (32 // 8)  # default: contiguous worst case
    cache = model.init_paged_cache(4, n_pages, 8)
    # identity page tables for slots 0/2; slots 1/3 unallocated
    pt = np.full((4, 4), -1, np.int32)
    pt[0] = np.arange(0, 4)
    pt[2] = np.arange(8, 12)
    tok = jnp.array([1, 2, 3, 4], jnp.int32)
    pos = jnp.array([0, 1, 2, 3], jnp.int32)
    active = jnp.array([True, False, True, False])
    logits, new_cache = b.fn(params, cache, jnp.asarray(pt), tok, pos, active)
    assert logits.shape == (4, model.config.vocab_size)
    k = np.asarray(new_cache.kv.k)  # [L, n_pages, ps, Hkv, hd]
    assert (k[:, 0, 0] != 0).any()   # slot 0: pos=0 -> page pt[0,0]=0 row 0
    assert (k[:, 8, 2] != 0).any()   # slot 2: pos=2 -> page pt[2,0]=8 row 2
    assert (k[:, 4:8] == 0).all()    # pages of inactive slots untouched
    assert (k[:, 0, 1:] == 0).all()  # only the written row changed
