"""Serving launcher: continuous batching with the fully-jitted engine.

  python -m repro.launch.serve --arch phi3-mini-3.8b --reduced \
      --requests 8 --max-new 16 --cache-len 128 --policy shortest-prompt

``--engine host-loop`` runs the pre-rewrite reference engine instead
(useful for eyeballing the speedup; ``benchmarks/serve_bench.py`` measures
it properly).
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_arch, reduced
from repro.launch import compile_cache
from repro.models.transformer import build_model
from repro.serve import Engine, HostLoopEngine, PrivacyLedger, Request
from repro.serve.scheduler import Scheduler


def gen_prompts(rng, n: int, prompt_len: int, vocab: int):
    """n random prompts with lengths in [min(4, prompt_len), prompt_len].
    Guarding the range here (rather than letting ``rng.integers`` throw
    its opaque ``high <= low`` error) is the --prompt-len < 4 fix: short
    maxima clamp the lower bound instead of crashing."""
    if prompt_len < 1:
        raise ValueError(f"--prompt-len must be >= 1, got {prompt_len}")
    lo = min(4, prompt_len)
    return [rng.integers(0, vocab, int(rng.integers(lo, prompt_len + 1)))
            for _ in range(n)]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--engine", choices=["jitted", "host-loop"],
                    default="jitted")
    ap.add_argument("--policy", choices=list(Scheduler.POLICIES),
                    default="fifo")
    ap.add_argument("--decode-chunk", type=int, default=16,
                    help="fused decode steps per dispatch "
                         "(floored to a power of two)")
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request deadline in seconds, measured from "
                         "just before the engine starts (cold-start jit "
                         "compilation counts against it)")
    ap.add_argument("--paged", action="store_true",
                    help="block-paged KV cache (attention-only archs)")
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="KV pool size in blocks (default: HBM-equal to "
                         "the contiguous max_batch x cache_len slabs)")
    ap.add_argument("--budget-eps", type=float, default=None,
                    help="per-user privacy budget: attach a ledger and "
                         "tag request i with user 'tenant-<i %% 4>'")
    ap.add_argument("--ledger-delta", type=float, default=1e-6)
    args = ap.parse_args()

    compile_cache.enable()
    arch = get_arch(args.arch)
    if args.reduced:
        arch = reduced(arch)
    assert not arch.embed_stub, "serve launcher drives token-input archs"
    model = build_model(arch, param_dtype="float32", compute_dtype="float32")
    params = model.init(jax.random.PRNGKey(args.seed))
    if args.engine == "host-loop":
        if args.deadline is not None or args.policy != "fifo":
            print("[serve] WARNING: --deadline/--policy are ignored by the "
                  "host-loop reference engine (FIFO, no eviction)")
        engine = HostLoopEngine(model, params, max_batch=args.max_batch,
                                cache_len=args.cache_len, seed=args.seed)
    else:
        from repro.serve.ledger import RequestCharge
        ledger = None
        if args.budget_eps is not None:
            # q=0.01, sigma=4.0 prices one request at eps ~0.0554 (delta
            # 1e-6), so e.g. --budget-eps 0.057 admits 4 requests per
            # tenant before refusing
            ledger = PrivacyLedger(
                args.budget_eps, args.ledger_delta, policy="refuse",
                default_charge=RequestCharge(sample_rate=0.01,
                                             noise_multiplier=4.0))
        engine = Engine(model, params, max_batch=args.max_batch,
                        cache_len=args.cache_len, seed=args.seed,
                        policy=args.policy, decode_chunk=args.decode_chunk,
                        record_ttft=True, paged=args.paged,
                        block_size=args.block_size,
                        num_blocks=args.num_blocks, ledger=ledger)
    rng = np.random.default_rng(args.seed)
    t0 = time.perf_counter()
    prompts = gen_prompts(rng, args.requests, args.prompt_len, arch.vocab)
    # deadline baseline sits after prompt generation, right before the
    # engine starts, so all requests get the full budget
    now = time.monotonic()
    deadline = None if args.deadline is None else now + args.deadline
    ledgered = args.engine == "jitted" and args.budget_eps is not None
    from repro.serve import BudgetExceeded
    for uid, prompt in enumerate(prompts):
        req = Request(uid=uid, prompt=prompt.astype(np.int32),
                      max_new=args.max_new, temperature=args.temperature,
                      deadline=deadline,
                      user=f"tenant-{uid % 4}" if ledgered else None)
        try:
            engine.submit(req)
        except BudgetExceeded as e:
            print(f"[serve] req {uid} REFUSED: {e}")
    out = engine.run()
    dt = time.perf_counter() - t0
    total_tokens = sum(len(v) for v in out.values())
    for uid in sorted(out):
        print(f"[serve] req {uid}: {out[uid]}")
    print(f"[serve] {len(out)} requests, {total_tokens} tokens "
          f"in {dt:.2f}s ({total_tokens/dt:.1f} tok/s)")
    print(f"[serve] stats: {engine.stats}")
    if getattr(engine, "ttft", None):
        ms = 1e3 * np.mean(list(engine.ttft.values()))
        print(f"[serve] mean time-to-first-token: {ms:.1f} ms")


if __name__ == "__main__":
    main()
