"""Serving benchmark: continuous batching over the paged KV cache.

Drives a synthetic mixed-length request trace through
``PagedServingEngine`` (serving/engine.py) and reports:

* throughput — generated tokens per second over the whole trace, and the
  per-token latency distribution (p50/p99 of per-step wall time divided by
  the live-slot count that step);
* the request-level knapsack plan (``serving/packer.py``) for the same
  trace — wave count and per-wave page/FLOP-model balance;
* page-pool behaviour — peak pages in use, peak utilization and the
  within-page token occupancy at the peak.

Everything that does not depend on the machine (token counts, step counts,
wave structure, peak page occupancy) is a deterministic function of the
trace alone — those fields are gated tightly in
``benchmarks/bench_baselines.json``; wall-clock fields get generous
one-sided bounds. Timing is a second engine run after a full warm-up run
over the same trace, so jit compilation (the decode step plus one prefill
variant per distinct prompt length) is excluded.

Runs in-process via ``python -m benchmarks.run --only serving`` or
standalone::

  PYTHONPATH=src python -m benchmarks.serving [--use-kernel]

Writes ``BENCH_serving.json`` and prints ``name,us_per_call,derived`` CSV
rows (no header) on stdout.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

BENCH_SERVING_JSON = "BENCH_serving.json"

# engine geometry — small enough for the CI CPU budget, large enough that
# the trace below needs several admission waves (slots and pages both bind)
PAGE_SIZE = 8
N_PAGES = 48
MAX_SLOTS = 4
MAX_SEQ_LEN = 64


def build_trace(seed: int = 0, n_requests: int = 12):
    """Deterministic mixed-length trace: short chat-like prompts, long
    document-like prompts and mid-size ones, with varying generation
    budgets. Returns (prompt_lens, max_news, prompts)."""
    rng = np.random.RandomState(seed)
    prompt_lens, max_news = [], []
    for i in range(n_requests):
        kind = i % 3
        if kind == 0:        # short prompt, longer generation
            s, m = int(rng.randint(4, 10)), int(rng.randint(10, 16))
        elif kind == 1:      # long prompt, short generation
            s, m = int(rng.randint(28, 44)), int(rng.randint(4, 8))
        else:                # mid-size both
            s, m = int(rng.randint(12, 24)), int(rng.randint(8, 12))
        prompt_lens.append(s)
        max_news.append(m)
    prompts = [rng.randint(0, 211, size=s).astype(np.int32)
               for s in prompt_lens]
    return prompt_lens, max_news, prompts


def _drive(engine, requests):
    """Run the trace through an engine step by step, timing each fused
    step launch. Returns (outputs, per_token_latency_us, wall_s,
    peak_pages, peak_util, peak_slot_util)."""
    for r in requests:
        engine.submit(r)
    lat_us = []
    peak_pages = peak_util = peak_slot = 0.0
    t_start = time.perf_counter()
    while engine.waiting or engine.live:
        t0 = time.perf_counter()
        done = engine.step()
        dt = time.perf_counter() - t0
        # one decode token per slot that took part in the fused step:
        # the still-live slots plus the ones retired this step
        produced = max(1, engine.n_live + len(done))
        lat_us.append(dt / produced * 1e6)
        u = engine.pm.utilization()
        peak_pages = max(peak_pages, u["pages_in_use"])
        peak_util = max(peak_util, u["pages_in_use"] / engine.pm.capacity)
        peak_slot = max(peak_slot, u["slot_utilization"])
    wall_s = time.perf_counter() - t_start
    return dict(engine.finished), lat_us, wall_s, peak_pages, peak_util, \
        peak_slot


def run_serving_bench(use_kernel: bool = False, seed: int = 0):
    """Build the trace, warm-compile on a throwaway engine, then time a
    fresh engine over the identical trace. Returns the JSON payload."""
    import jax

    from repro.configs.base import ModelConfig
    from repro.models.transformer import init_model
    from repro.serving.engine import PagedServingEngine, Request
    from repro.serving.packer import pack_report, plan_waves

    cfg = ModelConfig(name="serve_bench", arch_type="dense", n_layers=4,
                      d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                      vocab_size=211)
    params = init_model(jax.random.PRNGKey(seed), cfg)
    prompt_lens, max_news, prompts = build_trace(seed)
    requests = [Request(uid=i, prompt=p, max_new_tokens=m)
                for i, (p, m) in enumerate(zip(prompts, max_news))]

    def fresh_engine():
        return PagedServingEngine(params, cfg, page_size=PAGE_SIZE,
                                  n_pages=N_PAGES, max_slots=MAX_SLOTS,
                                  max_seq_len=MAX_SEQ_LEN,
                                  use_kernel=use_kernel)

    # knapsack plan for the same trace (advisory queue shaping; the report
    # is part of the artifact, the engine below uses FIFO admission)
    sizes = list(zip(prompt_lens, max_news))
    warm = fresh_engine()
    waves = plan_waves(sizes, page_size=PAGE_SIZE,
                       page_budget=warm.pm.capacity, max_slots=MAX_SLOTS)
    pack = pack_report(sizes, waves, page_size=PAGE_SIZE)

    # warm-up run compiles the fused decode step and one prefill per
    # distinct prompt length; the timed run below hits only caches
    warm.run(requests)
    assert warm.pm.n_free == warm.pm.capacity, "warm run leaked pages"

    engine = fresh_engine()
    outputs, per_tok_us, wall_s, peak_pages, peak_util, peak_slot = \
        _drive(engine, requests)
    per_tok_us = np.asarray(per_tok_us)
    assert engine.pm.n_free == engine.pm.capacity, "timed run leaked pages"
    for r in requests:      # warm and timed runs must agree exactly
        assert np.array_equal(outputs[r.uid], warm.finished[r.uid]), \
            f"warm/timed token mismatch for request {r.uid}"

    # each request generates max_new tokens: 1 at prefill + the rest from
    # fused decode steps (no EOS in the synthetic vocab trace)
    gen_decode = sum(len(outputs[r.uid]) - r.prompt_len - 1
                     for r in requests)
    gen_total = sum(len(outputs[r.uid]) - r.prompt_len for r in requests)
    tok_per_s = gen_total / wall_s if wall_s > 0 else 0.0

    payload = {
        "bench": "serving",
        "backend": jax.default_backend(),
        "use_kernel": bool(use_kernel),
        "engine": {"page_size": PAGE_SIZE, "n_pages": N_PAGES,
                   "max_slots": MAX_SLOTS, "max_seq_len": MAX_SEQ_LEN},
        "model": {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
                  "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads},
        "trace": {"n_requests": len(requests),
                  "prompt_lens": prompt_lens,
                  "max_new_tokens": max_news,
                  "prompt_tokens": int(sum(prompt_lens))},
        "pack": pack,
        "totals": {"generated_tokens": int(gen_total),
                   "decode_tokens": int(gen_decode),
                   "engine_steps": int(engine.n_steps)},
        "pages": {"capacity": int(engine.pm.capacity),
                  "peak_in_use": int(peak_pages),
                  "peak_utilization": float(peak_util),
                  "peak_slot_utilization": float(peak_slot)},
        "throughput": {"tokens_per_sec": float(tok_per_s),
                       "wall_s": float(wall_s)},
        "latency_us_per_token": {
            "p50": float(np.percentile(per_tok_us, 50)),
            "p99": float(np.percentile(per_tok_us, 99)),
            "mean": float(per_tok_us.mean())},
    }
    return payload


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--use-kernel", action="store_true",
                    help="route decode attention through the paged Pallas "
                         "kernel (interpret mode on CPU)")
    ap.add_argument("--out", default=BENCH_SERVING_JSON)
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    payload = run_serving_bench(use_kernel=args.use_kernel)
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"# wrote {args.out}", file=sys.stderr)

    t, lat, p = payload["totals"], payload["latency_us_per_token"], \
        payload["pack"]
    print(f"serving_throughput,"
          f"{payload['latency_us_per_token']['mean']:.1f},"
          f"tokens_per_sec={payload['throughput']['tokens_per_sec']:.1f};"
          f"generated={t['generated_tokens']};steps={t['engine_steps']}")
    print(f"serving_latency,{lat['p50']:.1f},"
          f"p50_us={lat['p50']:.1f};p99_us={lat['p99']:.1f}")
    print(f"serving_pack,0.0,"
          f"n_waves={p['n_waves']};wave_pages={p['wave_pages']};"
          f"cost_max_over_mean="
          f"{p['wave_cost_max'] / max(p['wave_cost_mean'], 1e-9):.3f}")
    print(f"serving_pages,0.0,"
          f"peak_in_use={payload['pages']['peak_in_use']};"
          f"peak_utilization={payload['pages']['peak_utilization']:.3f}")


if __name__ == "__main__":
    main()
