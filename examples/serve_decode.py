"""Serving demo: continuous-batching decode over a slot-managed KV cache.

A mixed-length Poisson request trace flows through ``serve.ServeLoop`` —
admission prefills each request into a free slot of ONE fixed-shape
DecodeCache (masked per-slot insert, no recompiles), every tick runs a
single slot-masked ``decode_step`` over all live requests, and EOS /
max-len retirement frees slots for immediate reuse.

    PYTHONPATH=src python examples/serve_decode.py --arch starcoder2-3b
    PYTHONPATH=src python examples/serve_decode.py --reduced --serial
    PYTHONPATH=src python examples/serve_decode.py --reduced --check
    PYTHONPATH=src python examples/serve_decode.py --reduced --paged --pages 16
    PYTHONPATH=src python examples/serve_decode.py --reduced --paged \
        --prefix-cache --prefill-chunk 16 --preempt  # §12.2 scheduler
    PYTHONPATH=src python examples/serve_decode.py --reduced --temperature 0.8

The model runs at its published widths unless ``--reduced`` picks the
tiny CPU-sized variant (configs/base.py ``reduced()``).

``--serial`` keeps the old request-at-a-time loop (the parity oracle);
``--check`` runs both and asserts token-for-token identical streams;
``--paged`` pools per-slot KV capacity into a shared page table
(``--pages`` bounds the pool — admission backpressures when exhausted);
``--temperature``/``--top-k`` sample instead of greedy argmax
(temperature 0 IS greedy, bit-identical).
"""
import argparse
import sys

import jax
import numpy as np

from repro.launch.compile_cache import use_compile_cache
from repro.models.model import build_model_by_name
from repro.serve import (
    PagedServeLoop,
    SamplerConfig,
    SerialLoop,
    ServeLoop,
    ServeUnsupportedError,
    poisson_trace,
)


def clone(reqs):
    return [r.clone() for r in reqs]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="starcoder2-3b")
    ap.add_argument("--reduced", action="store_true",
                    help="tiny CPU-sized widths (default: the published "
                    "widths, which need an accelerator)")
    ap.add_argument("--slots", type=int, default=8, help="B_slots")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--rate", type=float, default=2.0, help="arrivals/tick")
    ap.add_argument("--capacity", type=int, default=128,
                    help="KV slots per cache row")
    ap.add_argument("--max-new", type=int, default=16,
                    help="largest per-request decode budget")
    ap.add_argument("--cache-update", default="mask",
                    choices=("mask", "scatter"))
    ap.add_argument("--serial", action="store_true",
                    help="old request-at-a-time loop")
    ap.add_argument("--check", action="store_true",
                    help="run BOTH loops and assert token parity")
    ap.add_argument("--paged", action="store_true",
                    help="pooled-page KV cache (PagedServeLoop)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV rows per page (--paged)")
    ap.add_argument("--pages", type=int, default=None,
                    help="pool size in pages (--paged; default = the "
                    "contiguous worst case, fewer pages = backpressure)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share page-aligned prompt prefixes read-only "
                    "across requests (--paged; §12.2)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="prefill at most N prompt tokens per tick, "
                    "interleaved with decode (--paged)")
    ap.add_argument("--preempt", action="store_true",
                    help="evict the youngest live request to host staging "
                    "when the FIFO head starves (--paged)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy, bit-identical)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k sampling cutoff (0 = full vocab)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    sampler = SamplerConfig(temperature=args.temperature, top_k=args.top_k,
                            seed=args.seed)
    model = build_model_by_name(args.arch, reduced=args.reduced)
    cfg = model.config
    try:  # fail fast + clearly (whisper: no decode path; vlm: no patches;
        # xlstm: no KV to page)
        if args.paged:
            serve_loop = PagedServeLoop(
                model, params=None, n_slots=args.slots,
                capacity=args.capacity, page_size=args.page_size,
                n_pages=args.pages, cache_update=args.cache_update,
                sampler=sampler, prefix_cache=args.prefix_cache,
                prefill_chunk=args.prefill_chunk, preempt=args.preempt)
        else:
            serve_loop = ServeLoop(model, params=None, n_slots=args.slots,
                                   capacity=args.capacity,
                                   cache_update=args.cache_update,
                                   sampler=sampler)
    except ServeUnsupportedError as e:
        print(f"serve_decode: {e}", file=sys.stderr)
        sys.exit(2)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))  # one program
    serve_loop.params = params

    reqs = poisson_trace(
        args.requests, rate=args.rate,
        plen_choices=(8, 16, 24, 32),
        max_new_choices=tuple(sorted({max(1, args.max_new // 4),
                                      max(1, args.max_new // 2),
                                      args.max_new})),
        vocab_size=cfg.vocab_size, seed=args.seed,
    )
    if cfg.vision_dim:  # vlm requests carry their vision input
        pr = np.random.RandomState(args.seed + 1)
        for q in reqs:
            q.patches = pr.randn(cfg.num_patches,
                                 cfg.vision_dim).astype(np.float32)
    print(f"{args.arch}: {len(reqs)} requests, plens "
          f"{sorted({r.plen for r in reqs})}, window="
          f"{cfg.sliding_window or 'full'}")

    def run_loop(rs):
        return serve_loop.run(rs)

    def run_serial(rs):
        return SerialLoop(model, params, cache_update=args.cache_update,
                          sampler=sampler).run(rs)

    if args.check:
        a, b = clone(reqs), clone(reqs)
        run_loop(a)
        run_serial(b)
        for ra, rb in zip(a, b):
            assert ra.out == rb.out, (
                f"request {ra.rid}: loop {ra.out} != serial {rb.out}")
        print(f"PARITY OK: {len(a)} requests token-for-token identical")
        return

    stats = run_serial(reqs) if args.serial else run_loop(reqs)
    mode = "serial" if args.serial else \
        ("paged" if args.paged else "loop") + f"[slots={args.slots}]"
    print(f"{mode}: {stats['tokens']} tokens in {stats['wall_s']:.2f}s "
          f"({stats['tok_s']:.1f} tok/s, "
          f"{stats['decode_dispatches']} decode dispatches, "
          f"{stats['prefill_dispatches']} prefills)")
    if args.paged and not args.serial:
        print(f"pool: {stats['peak_pages']}/{stats['n_pages']} peak pages "
              f"of {stats['page_size']} rows")
        if args.prefix_cache or args.prefill_chunk or args.preempt:
            print(f"scheduler: {stats['prefix_hit_tokens']} prefix-hit "
                  f"tokens, {stats['prefilled_tokens']} prefilled, "
                  f"{stats['extend_dispatches']} chunk dispatches, "
                  f"{stats['preemptions']} preemptions")
    print("first request ids:", np.asarray(reqs[0].out))


if __name__ == "__main__":
    main()
