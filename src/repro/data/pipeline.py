"""Deterministic synthetic LM data pipeline.

Production-shaped: host-sharded (each host materializes only its shard),
seeded per (step, host) so a restarted/elastic worker can resume mid-stream
without replay, with double-buffered prefetch and optional sequence packing.
"""
from __future__ import annotations

import queue
import threading
from dataclasses import dataclass
from typing import Dict, Iterator

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.axes import batch_pspec, mesh_info


@dataclass(frozen=True)
class DataConfig:
    global_batch: int
    seq_len: int
    vocab_size: int
    seed: int = 1234
    microbatch: int = 0          # reshape to [n, B/n, ...] when > 1
    pack: bool = True            # synth docs packed to seq_len with EOS
    eos_id: int = 2


def _host_tokens(cfg: DataConfig, step: int, start: int, count: int):
    """Deterministic tokens for rows [start, start+count) of global batch.

    Ids over ``[3, vocab)`` follow a Zipf law (rank ``r`` has weight
    ``1/r``, as word frequencies in text do), so a model has something to
    learn: on uniform ids no model's loss falls below ``ln(vocab)``."""
    rng = np.random.default_rng(
        np.random.SeedSequence([cfg.seed, step, start]))
    cdf = np.cumsum(1.0 / np.arange(1, cfg.vocab_size - 2))
    ranks = np.searchsorted(cdf, rng.random((count, cfg.seq_len + 1))
                            * cdf[-1])
    toks = (3 + ranks).astype(np.int32)
    if cfg.pack:
        # synthetic doc boundaries every ~512 tokens
        doc_len = rng.integers(256, 1024)
        toks[:, ::max(int(doc_len), 1)] = cfg.eos_id
    return toks


def make_batch(cfg: DataConfig, step: int) -> Dict[str, np.ndarray]:
    """Whole global batch on one host (single-host container)."""
    toks = _host_tokens(cfg, step, 0, cfg.global_batch)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    if cfg.microbatch > 1:
        n = cfg.microbatch
        batch = {k: v.reshape(n, cfg.global_batch // n, cfg.seq_len)
                 for k, v in batch.items()}
    return batch


class Prefetcher:
    """Double-buffered background prefetch onto device."""

    def __init__(self, cfg: DataConfig, mesh, start_step: int = 0,
                 ctx_shape=None, depth: int = 2):
        self.cfg = cfg
        self.mesh = mesh
        info = mesh_info(mesh)
        micro_b = cfg.global_batch // max(cfg.microbatch, 1)
        bp = batch_pspec(info, micro_b)
        entries = ((None,) if cfg.microbatch > 1 else ()) + tuple(bp)
        self.sharding = NamedSharding(mesh, P(*entries))
        self.ctx_shape = ctx_shape
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._step = start_step
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._work, daemon=True)
        self._thread.start()

    def _work(self):
        # host-side generation only — the device_put happens on the consumer
        # thread (concurrent multi-threaded dispatch can deadlock XLA:CPU's
        # intra-process collective rendezvous; on TPU pods the transfer would
        # be a separate DMA engine anyway)
        step = self._step
        while not self._stop.is_set():
            batch = make_batch(self.cfg, step)
            if self.ctx_shape is not None:
                rng = np.random.default_rng([self.cfg.seed, step, 7])
                ctx = rng.standard_normal(self.ctx_shape).astype(np.float32)
                if self.cfg.microbatch > 1:
                    n = self.cfg.microbatch
                    ctx = ctx.reshape((n, ctx.shape[0] // n) + ctx.shape[1:])
                batch["ctx"] = ctx * 0.02
            while not self._stop.is_set():
                try:
                    self._q.put((step, batch), timeout=0.5)
                    step += 1
                    break
                except queue.Full:
                    continue

    def __iter__(self) -> Iterator:
        return self

    def __next__(self):
        step, batch = self._q.get()
        dev = {k: jax.device_put(v, self.sharding) for k, v in batch.items()}
        return step, dev

    def close(self):
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=5)
