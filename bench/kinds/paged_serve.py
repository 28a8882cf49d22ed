"""Cells of paged serving: PagedServeLoop.tick under a wall-clock queue.

Set-up makes the weights on the device from the seed, builds the loop as
the configuration's ``serve`` block states, and warms up every program
the mix will use: the prefill of each prompt length on the mix's grid,
the page insert and the decode step, through the loop's own jits. A
backlog mix then fills the slots before the window opens; an open-loop
mix starts empty. In the window the harness calls ``tick`` and releases
each request at its wall-clock due time, whatever the ticks are doing.

After the window: requests due in it but without a first token are
served on (no new arrivals) until each has one, at most a minute, so a
late first token counts its wait. Then the program's state is freed and
the plain reference checks a sample of the finished requests.
"""
from __future__ import annotations

import gc
import time
from collections import deque

LATE_S = 60.0


class WallClockQueue:
    """Requests released at wall-clock due times, in due order.

    The serve loop asks ``peek_arrived(tick)`` / ``pop_arrived(tick)``;
    the tick number is ignored: a request is visible once its due time
    has passed. ``horizon`` stops releases at that time (the close of the
    window). ``popped`` records when each request was taken for
    admission."""

    def __init__(self, reqs, due):
        self._q = deque(sorted(zip(due, range(len(reqs)), reqs)))
        self.horizon = float("inf")
        self.popped = {}

    def __len__(self) -> int:
        return len(self._q)

    def next_due(self) -> float:
        return self._q[0][0] if self._q else float("inf")

    def _ready(self) -> bool:
        if not self._q:
            return False
        due = self._q[0][0]
        return due <= time.time() and due < self.horizon

    def peek_arrived(self, tick):
        return self._q[0][2] if self._ready() else None

    def pop_arrived(self, tick):
        if not self._ready():
            return None
        req = self._q.popleft()[2]
        self.popped[req.rid] = time.time()
        return req


def arch_config(cfg: dict):
    """The program's ArchConfig for a configuration file."""
    from repro.configs.base import ArchConfig

    return ArchConfig(
        name=cfg["name"], family="dense", source=cfg["source"],
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope=True, rope_theta=cfg["rope_theta"],
        sliding_window=cfg["sliding_window"], mlp_act="gelu",
        mlp_bias=cfg["use_bias"], qkv_bias=cfg["use_bias"],
        norm="layernorm", tie_embeddings=cfg["tie_word_embeddings"],
        param_dtype=cfg["served_dtype"], compute_dtype=cfg["served_dtype"])


def _build(cell, seed):
    import jax

    from repro.models.model import build_model
    from repro.serve import PagedServeLoop

    from bench.traffic import derive

    cfg, srv = cell.config, cell.config["serve"]
    ref = cell.reference()
    model = build_model(arch_config(cfg))
    key = jax.random.PRNGKey(derive(seed, "params"))
    params = jax.block_until_ready(jax.jit(
        lambda k: ref.init_params(cfg, k))(key))
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
            zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError(f"{cell.config_name}: the configuration's sizes do "
                         "not give the program's parameters")
    loop = PagedServeLoop(model, params, n_slots=srv["slots"],
                          page_size=srv["page_size"], n_pages=srv["pages"],
                          cache_update=srv["cache_update"])
    return model, params, loop, key


def _drain(loop, reqs):
    from repro.serve import RequestQueue

    q = RequestQueue(reqs)
    while len(q) or loop.table.any_active():
        loop.tick(q)


def _warm_up(loop, cell, vocab):
    """Every prompt length of the mix's grid once (two tokens out each):
    compiles or loads each prefill, the insert and the decode step."""
    import numpy as np

    from repro.serve import Request

    from bench.traffic import length_grid

    rng = np.random.RandomState(0)
    grid = length_grid(cell.traffic["prompt"])
    _drain(loop, [Request(rid=1_000_000 + i,
                          tokens=rng.randint(0, vocab, n), max_new=2)
                  for i, n in enumerate(grid)])


def _sample(finished, target_tokens, rng):
    """The longest finished request, then others drawn from the seed,
    until the sample holds ``target_tokens`` served tokens."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: (r.plen + len(r.out), r.rid))
    pick = [order.pop()]
    rest = [order[i] for i in rng.permutation(len(order))]
    while rest and sum(len(r.out) for r in pick) < target_tokens:
        pick.append(rest.pop())
    return pick


def serve_window(loop, cell, seed, seconds, vocab, trace, trace_dir):
    """Run the mix for ``seconds``; -> (requests, counters)."""
    import jax

    from repro.analysis.sanitize import Sanitizer
    from repro.serve import Request

    from bench import reduce
    from bench.traffic import serve_requests

    tr = cell.traffic
    spec = serve_requests(tr, vocab, seed)
    reqs = [Request(rid=rid, tokens=toks, max_new=out)
            for rid, toks, out, _ in spec]
    offsets = [due for *_, due in spec]
    backlog = tr["arrivals"]["kind"] == "backlog"
    san = Sanitizer(nan_checks=False, label=cell.name)
    with san:
        _warm_up(loop, cell, vocab)
        jax.block_until_ready(loop.cache)
        if backlog:
            t_fill = time.time()
            queue = WallClockQueue(reqs, [t_fill + o for o in offsets])
            while loop.table.free_slots() and len(queue):
                loop.tick(queue)
            jax.block_until_ready(loop.cache)
        if trace:
            reduce.start(trace_dir)
            span = jax.profiler.TraceAnnotation(reduce.WINDOW_SPAN)
            span.__enter__()
        san.mark_steady()
        t0 = time.time()
        if not backlog:
            queue = WallClockQueue(reqs, [t0 + o for o in offsets])
        t_end = t0 + seconds
        d0, p0 = loop.decode_dispatches, loop.prefill_dispatches
        while True:
            now = time.time()
            if now >= t_end:
                break
            if not loop.table.any_active() and queue.next_due() > now:
                with _span(trace, "bench:wait"):
                    time.sleep(min(queue.next_due(), t_end) - now)
                continue
            with _span(trace, "bench:tick"):
                loop.tick(queue)
        t1 = time.time()
        compiles = san.steady_compiles
        d1, p1 = loop.decode_dispatches, loop.prefill_dispatches
        if trace:
            span.__exit__(None, None, None)
            jax.profiler.stop_trace()
    # first tokens still owed to requests due in the window
    queue.horizon = t_end
    due_in = [r for r, o in zip(reqs, offsets)
              if (t0 if backlog else t0 + o) < t_end]
    late_stop = t1 + LATE_S
    while any(not r.out and r.failed is None for r in due_in) \
            and time.time() < late_stop and not backlog:
        loop.tick(queue)
    due_wall = {r.rid: (t_fill if backlog else t0) + o
                for r, o in zip(reqs, offsets)}
    counters = dict(t0=t0, t_end=t_end, decode_dispatches=d1 - d0,
                    prefill_dispatches=p1 - p0, compiles_in_window=compiles,
                    popped=dict(queue.popped), backlog=backlog,
                    due_wall=due_wall, due_in=[r.rid for r in due_in])
    return reqs, counters


class _span:
    def __init__(self, on, name):
        self.on, self.name = on, name

    def __enter__(self):
        if self.on:
            import jax

            self.a = jax.profiler.TraceAnnotation(self.name)
            self.a.__enter__()

    def __exit__(self, *exc):
        if self.on:
            self.a.__exit__(*exc)


def measure(reqs, counters, cell) -> dict:
    """End-to-end numbers and per-layer counters of one window, from the
    requests' own token timestamps."""
    from bench import stats, work

    cfg = cell.config
    t0, t_end = counters["t0"], counters["t_end"]
    win = t_end - t0
    tokens, gaps, dec_pos, pre_plens = 0, [], [], []
    for r in reqs:
        w = r.tok_walls
        for j, t in enumerate(w):
            if not t0 <= t < t_end:
                continue
            tokens += 1
            if j == 0:
                pre_plens.append(r.plen)
            else:
                dec_pos.append(r.plen + j - 1)
                if w[j - 1] >= t0:
                    gaps.append(t - w[j - 1])
    by_rid = {r.rid: r for r in reqs}
    due_in = [by_rid[i] for i in counters["due_in"]]
    ttft = [(r.tok_walls[0] if r.tok_walls else float("inf"))
            - counters["due_wall"][r.rid] for r in due_in]
    dd = counters["decode_dispatches"]
    c = dict(counters)
    c.update(
        window_s=win, tokens=tokens, decode_tokens=len(dec_pos),
        slot_occupancy=len(dec_pos) / dd if dd else None,
        attn_bytes=sum(work.paged_attn_bytes(cfg, p) for p in dec_pos)
        * cfg["num_hidden_layers"],
        attn_flops=sum(work.paged_attn_flops(cfg, p) for p in dec_pos)
        * cfg["num_hidden_layers"],
        model_flops=sum(work.decoder_token_flops(cfg, p) for p in dec_pos)
        + sum(work.prefill_flops(cfg, n) for n in pre_plens))
    e2e = {"serve_tok_s": tokens / win,
           "itl_p95_ms": 1e3 * stats.percentile(gaps, 95)}
    if not counters["backlog"]:
        e2e["ttft_p90_ms"] = 1e3 * stats.percentile(ttft, 90)
    return e2e, c


def run(cell, seed: int, seconds: float, trace: bool, t_process: float,
        trace_dir=None) -> dict:
    return _run(cell, seed, seconds, trace, t_process, trace_dir)[0]


def _run(cell, seed, seconds, trace, t_process, trace_dir):
    """-> (the run's result, (weights' key, the checked requests))"""
    import numpy as np

    from bench import harness
    from bench.traffic import derive

    cfg = cell.config
    model, params, loop, key = _build(cell, seed)
    reqs, counters = serve_window(loop, cell, seed, seconds,
                                  cfg["vocab_size"], trace, trace_dir)
    device = harness.device_info(cell.chips)
    e2e, c = measure(reqs, counters, cell)
    e2e["setup_s"] = counters["t0"] - t_process
    t_end = counters["t_end"]
    finished = [r for r in reqs if r.failed is None and r.finished()
                and r.tok_walls and r.tok_walls[-1] < t_end]
    pick = _sample(finished, cell.traffic["check_tokens"],
                   np.random.RandomState(derive(seed, "check")))
    items = [(r.tokens.copy(), np.asarray(r.out, np.int32)) for r in pick]
    touched = [r for r in reqs if r.rid in set(counters["due_in"])
               or any(counters["t0"] <= t < t_end for t in r.tok_walls)]
    failed = sum(r.failed is not None for r in touched)
    del loop, params, model
    gc.collect()

    t_check = time.perf_counter()
    readings = {}
    if items:
        gap, mean, n = cell.reference().served_gaps(cfg, key, items)
        readings = {"served_gap": gap, "mean_gap": mean, "checked_tokens": n}
    check_s = time.perf_counter() - t_check
    checks = harness.judge(readings, cell.limits)
    return dict(e2e=e2e, attempted=len(touched), failed=failed,
                correct=harness.passed(checks) and failed == 0,
                checks=checks, readings=readings, device=device, counters=c,
                check_s=check_s), (key, items)


def control(cell, seed: int, seconds: float) -> dict:
    """A run of the program, and beside its readings (``control``) those
    of the int8 control on the same checked requests: at each position of
    the same prompts and served tokens, the token the control puts first,
    judged by the same reference."""
    out, (key, items) = _run(cell, seed, seconds, False, time.time(), None)
    ctl = {}
    if items:
        gap, mean, n = cell.reference().served_gaps(cell.config, key, items,
                                                    mode="int8")
        ctl = {"served_gap": gap, "mean_gap": mean, "checked_tokens": n}
    return dict(out, control=ctl)
