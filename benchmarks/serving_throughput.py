"""Serving-path throughput ladder: dense -> paged -> paged+prefix ->
paged+prefix+speculative, on a shared-prefix workload.

Runs the real continuous-batching engine (reduced 1.8B, 1-device CPU
mesh) over the same request set in all four configurations and reports
tokens/s and mean TTFT per variant.  The *deterministic* fields — engine
steps, decoded tokens, prefix hit rate, speculative accept rate, and the
ladder orderings — go into BENCH_<tag>.json for the perf-trajectory gate;
wall-clock numbers stay in results/bench_report.json (host-dependent).

The ladder's contract on a shared-prefix workload:

* every variant emits token-identical output (greedy equivalence),
* paged+prefix finishes in strictly fewer engine steps than dense
  (prefix hits skip the shared prefill span),
* speculation finishes in strictly fewer steps than paged+prefix
  (each accepted draft token saves a target forward).
"""
from __future__ import annotations

import time

import numpy as np

SLOTS = 2
MAX_SEQ = 48
PAGE_SIZE = 8
N_REQ = 6
MAX_NEW = 6
SPEC_K = 3


def _prompts(vocab):
    """Shared-prefix request mix: block-aligned reuse, mid-block
    divergence, and hits shorter/longer than one page."""
    rng = np.random.default_rng(7)
    base = rng.integers(3, vocab, 12, dtype=np.int32)
    out = []
    for i in range(N_REQ):
        keep = (6, 12, 9, 12, 6, 9)[i]
        tail = rng.integers(3, vocab, 3 + (i % 3), dtype=np.int32)
        out.append(np.concatenate([base[:keep], tail]).astype(np.int32))
    return out


def _run_variant(cfg, mesh, prompts, **eng_kw):
    from repro.serving import Request, ServingEngine
    eng = ServingEngine(cfg, mesh, slots=SLOTS, max_seq=MAX_SEQ, **eng_kw)
    eng.load(seed=0)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=MAX_NEW)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    ttft = {}
    t0 = time.perf_counter()
    while (not eng.queue.empty() or eng._pending is not None
           or any(a is not None for a in eng.active)):
        eng.step()
        now = time.perf_counter()
        for r in reqs:
            if r.out_tokens and r.rid not in ttft:
                ttft[r.rid] = now - t0
    wall = time.perf_counter() - t0
    stats = dict(eng.stats)
    row = {
        "steps": stats["steps"],
        "decoded_tokens": stats["decoded_tokens"],
        "tok_per_s": round(stats["decoded_tokens"] / max(wall, 1e-9), 1),
        "ttft_ms": round(1e3 * sum(ttft.values()) / max(len(ttft), 1), 2),
        "wall_s": round(wall, 3),
    }
    if eng.paged is not None:
        row["prefix_hit_rate"] = round(
            stats["prefix_hit_tokens"] / max(stats["prompt_tokens"], 1), 4)
        row["cow"] = eng.paged.stats["cow"]
    if eng.spec_k:
        row["spec_accept_rate"] = round(
            stats["spec_accepted"] / max(stats["spec_proposed"], 1), 4)
    return row, [r.out_tokens for r in reqs]


def run():
    from repro.configs.registry import get_config
    from repro.core import compat

    mesh = compat.make_mesh((1, 1), ("data", "model"))
    cfg = get_config("internlm2-1.8b").reduced().replace(dtype="float32")
    prompts = _prompts(cfg.vocab_size)

    variants = {}
    tokens = {}
    variants["dense"], tokens["dense"] = _run_variant(cfg, mesh, prompts)
    variants["paged"], tokens["paged"] = _run_variant(
        cfg, mesh, prompts, paged=True, page_size=PAGE_SIZE)
    variants["paged_prefix"], tokens["paged_prefix"] = _run_variant(
        cfg, mesh, prompts, paged=True, page_size=PAGE_SIZE,
        prefix_cache=True)
    variants["paged_prefix_spec"], tokens["paged_prefix_spec"] = \
        _run_variant(cfg, mesh, prompts, paged=True, page_size=PAGE_SIZE,
                     prefix_cache=True, draft=cfg, spec_k=SPEC_K)

    ref = tokens["dense"]
    section = {
        "workload": {"slots": SLOTS, "max_seq": MAX_SEQ,
                     "page_size": PAGE_SIZE, "requests": N_REQ,
                     "max_new": MAX_NEW, "spec_k": SPEC_K,
                     "arch": cfg.name},
        "variants": variants,
        "token_identical": all(tokens[v] == ref for v in tokens),
        # step counts are deterministic; wall clock is not — the BENCH
        # gate pins the ladder on steps, not seconds
        "paged_prefix_beats_dense":
            variants["paged_prefix"]["steps"] < variants["dense"]["steps"],
        "spec_beats_paged_prefix":
            variants["paged_prefix_spec"]["steps"]
            < variants["paged_prefix"]["steps"],
    }
    return section


def bench_fields(section):
    """The deterministic subset pinned into BENCH_<tag>.json."""
    return {
        "steps": {v: row["steps"] for v, row in section["variants"].items()},
        "decoded_tokens": section["variants"]["dense"]["decoded_tokens"],
        "prefix_hit_rate":
            section["variants"]["paged_prefix"]["prefix_hit_rate"],
        "spec_accept_rate":
            section["variants"]["paged_prefix_spec"]["spec_accept_rate"],
        "token_identical": section["token_identical"],
        "paged_prefix_beats_dense": section["paged_prefix_beats_dense"],
        "spec_beats_paged_prefix": section["spec_beats_paged_prefix"],
    }
