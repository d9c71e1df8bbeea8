"""The one generator behind every traffic mix.

A mix is a data file ``bench/traffic/<mix>.json``.  Each part of it that
names a ``law`` is drawn by that law's module,
``bench/traffic/laws/<law>.py``, found by the name; this module puts the
parts together, from ``--seed``, into training batches or a serving arrival
schedule.  A new law is a new file there, and a new mix a new data file.
The program under test only ever sees the generated inputs.

What a law module gives, by the part it draws:

* ``tokens``: ``ids(part, rng, shape, vocab)``, int32 ids;
* ``prompt_len``, ``output_len``: ``sizes(part, n)``, the same multiset
  for every seed;
* ``arrivals``: ``gaps(part, seconds)``, the same gaps for every seed,
  one per request due in the window;
* ``prompts``: ``prompts(part, lengths, stream, ids)``, one prompt a
  length;
* each of ``rewrite`` (training): ``apply(part, rng, toks)``, which
  changes the batch in place.

Every seed gets the same multiset of sizes and arrival gaps, in an order
of its own, so two seeds do the same amount of work and differ only in
which request comes when and in the token ids.
"""
from __future__ import annotations

import queue
import re
import threading
from dataclasses import dataclass
from pathlib import Path
from typing import List

import numpy as np

LAWS = Path(__file__).resolve().parent / "traffic" / "laws"
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def seed_words(seed: int) -> List[int]:
    """A seed of any size as 32-bit words, for ``SeedSequence``."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed {seed} is negative")
    return [seed & 0xFFFFFFFF, seed >> 32]


def rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence(seed_words(seed) + list(stream)))


def law(part: dict):
    """The module of the law that a part of a mix names."""
    from harness import _load
    name = part["law"]
    path = LAWS / f"{name}.py"
    if not _NAME.match(name) or not path.exists():
        raise ValueError(f"unknown traffic law {name!r}: no {path}")
    return _load(path, "bench_law_" + name.replace(".", "_"))


# --------------------------------------------------------------------------
# training batches
# --------------------------------------------------------------------------
def train_batch(traffic: dict, *, seed: int, step: int, batch: int,
                seq_len: int, vocab: int) -> np.ndarray:
    """Tokens ``[batch, seq_len + 1]`` of step ``step``: inputs are
    ``[:, :-1]`` and labels ``[:, 1:]``.  Every step's rows differ."""
    g = rng(seed, 1, step)
    part = traffic["tokens"]
    toks = law(part).ids(part, g, (batch, seq_len + 1), vocab)
    for part in traffic.get("rewrite", []):
        law(part).apply(part, g, toks)
    return toks


class TrainFeed:
    """Batches placed on the trainer's batch sharding, double-buffered: a
    host thread makes the tokens, the consumer's ``next()`` places them,
    so the host-to-device copy is part of every step."""

    def __init__(self, traffic: dict, *, seed: int, batch: int,
                 seq_len: int, vocab: int, sharding, microbatch: int = 1,
                 start: int = 0):
        self.kw = dict(seed=seed, batch=batch, seq_len=seq_len, vocab=vocab)
        self.traffic = traffic
        self.sharding = sharding
        self.micro = max(microbatch, 1)
        self._q: "queue.Queue" = queue.Queue(
            maxsize=traffic.get("prefetch_depth", 2))
        self._stop = threading.Event()
        self._step = start
        self._thread = threading.Thread(target=self._work, daemon=True)
        self._thread.start()

    def _work(self):
        step = self._step
        while not self._stop.is_set():
            toks = train_batch(self.traffic, step=step, **self.kw)
            item = (step, toks)
            while not self._stop.is_set():
                try:
                    self._q.put(item, timeout=0.2)
                    step += 1
                    break
                except queue.Full:
                    continue

    def _shape(self, a: np.ndarray) -> np.ndarray:
        if self.micro > 1:
            return a.reshape(self.micro, a.shape[0] // self.micro,
                             a.shape[1])
        return a

    def __next__(self):
        import jax
        step, toks = self._q.get()
        batch = {"tokens": self._shape(np.ascontiguousarray(toks[:, :-1])),
                 "labels": self._shape(np.ascontiguousarray(toks[:, 1:]))}
        return step, {k: jax.device_put(v, self.sharding)
                      for k, v in batch.items()}

    def close(self):
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=10)


# --------------------------------------------------------------------------
# serving arrivals
# --------------------------------------------------------------------------
@dataclass
class Arrival:
    rid: int
    due_s: float            # offset from the window's start
    prompt: np.ndarray      # int32 ids
    max_new: int


def serve_schedule(traffic: dict, *, seed: int, seconds: float,
                   vocab: int) -> List[Arrival]:
    """Every request due in a window of ``seconds``, with the arrival
    law's gaps, sizes and prompts in an order drawn from ``seed``."""
    arrivals, tokens = traffic["arrivals"], traffic["tokens"]
    gaps = law(arrivals).gaps(arrivals, seconds)
    n = len(gaps)                         # the last is due at the close
    plen, olen = (law(traffic[k]).sizes(traffic[k], n)
                  for k in ("prompt_len", "output_len"))
    g = rng(seed, 2)
    gaps, plen, olen = (g.permutation(gaps), g.permutation(plen),
                        g.permutation(olen))
    due = np.cumsum(gaps) - gaps[0]       # the first is due at the start

    def ids(r: np.random.Generator, k: int) -> np.ndarray:
        return law(tokens).ids(tokens, r, (k,), vocab)

    prompts = law(traffic["prompts"]).prompts(
        traffic["prompts"], plen, lambda *s: rng(seed, *s), ids)
    return [Arrival(i, float(due[i]), prompts[i], int(olen[i]))
            for i in range(n)]
