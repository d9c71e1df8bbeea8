"""Batched serving engine: prefill + decode with slot-based continuous
batching, sharded over TMP / pipeline meshes.

The engine owns a fixed pool of ``slots`` (the decode batch dimension).
Requests are admitted into free slots (prompt consumption fills the slot's
KV range), every engine step decodes one token for all active slots, and
finished sequences free their slots for the admission queue — continuous
batching without re-compiling (all shapes static).

Parallel serving: the engine is mesh-agnostic — ``lm.build_decode`` routes
the decode matmuls through the same ``TmpCtx`` schedules as training (1D
and 2D TMP layouts; ``schedule="fused"`` rings the projection collectives
over the slot batch), shards the KV cache head-wise alongside the attention
weights, and on a ``pipe`` mesh streams decode micro-steps through the
stages (stage ``s`` decodes micro-group ``g`` while stage ``s-1`` decodes
``g+1`` — ``core/pipeline.decode_stream``).  Greedy decode is
token-identical to the single-device engine on every such mesh
(tests/_scripts/serving_equivalence.py).

Serving at scale (``--paged`` / ``--prefix-cache`` / ``--draft --spec-k``):

* **Paged KV** (``paged=True``): GLOBAL_ATTN caches live in a flat page
  pool instead of dense per-slot rows; a host-side
  :class:`repro.serving.paged_cache.PagedKVCache` allocates fixed-size
  blocks on demand and the engine passes each slot's block table (plus
  copy-on-write page pairs) into the jitted step every tick.  Admission
  becomes reservation-based: a request is only admitted when the free
  list plus evictable prefix pages cover its worst case, and a request
  that does not fit waits in a one-deep ``_pending`` buffer (cache-full
  backpressure) instead of deadlocking mid-decode.
* **Prefix cache** (``prefix_cache=True``): prompts are hashed at block
  granularity; a hit maps the donor's pages into the new slot's table
  (refcounted, COW on first divergent write) and skips prefill for the
  shared span — the slot starts at ``pos = hit`` with the remaining
  prompt teacher-forced as usual.
* **Speculative decoding** (``draft=<ArchConfig>, spec_k=k``): a small
  draft model proposes ``k`` tokens per round (plus one catch-up step
  re-consuming ``prev_tok`` to repair its cache after a rejected tail);
  the target verifies all ``k+1`` tokens in one batched ``lm.build_verify``
  forward and the engine accepts the longest agreeing run.  Greedy
  acceptance is *exactly* token-identical to undrafted decode: the
  verify forward returns, for every position, what single-token decode
  would have emitted there, so a divergence yields the oracle's own
  correction and full agreement yields a free bonus token.
"""
from __future__ import annotations

import queue
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import GLOBAL_ATTN, ArchConfig, TrainHParams
from repro.models import lm
from repro.models import params as prm
from repro.serving.paged_cache import PagedKVCache


@dataclass
class Request:
    """One generation request.  The engine stamps ``time.perf_counter()``
    at :meth:`ServingEngine.submit` (``t_submit``), at admission to a slot
    (``t_admit``) and when the first output token is appended
    (``t_first``): queue wait is ``t_admit - t_submit``, the prompt path
    ``t_first - t_admit``."""
    rid: int
    prompt: np.ndarray              # [prompt_len] int32
    max_new_tokens: int = 16
    out_tokens: List[int] = field(default_factory=list)
    done: bool = False
    t_submit: Optional[float] = None
    t_admit: Optional[float] = None
    t_first: Optional[float] = None


class ServingEngine:
    """``prefill_len`` is the admission contract: the longest prompt a
    request may carry (longer prompts fail at :meth:`submit`, not deep in
    the decode loop).  It defaults to half of ``max_seq`` so a prompt-full
    slot still has decode headroom; pass an explicit value to trade prompt
    capacity against generation length (``launch/serve.py --prefill-len``).

    ``decode_micro``: micro-group count for pipeline-mesh decode streaming
    (0 = auto: one group per stage, ``pp * virtual_stages``).

    ``plan``: an executable :class:`repro.core.plan.ParallelPlan` — the
    engine projects it onto its hp (schedule/layout/virtual stages) and
    ``decode_micro``.  Mixed per-layer *schedules* serve under the plan's
    ``primary_schedule`` (all schedules are token-identical at decode;
    only overlap differs); mixed per-layer *degrees* are a training-only
    layout and are rejected with a friendly error.

    ``paged`` switches GLOBAL_ATTN KV to the page-pool layout
    (``pages`` physical pages of ``page_size`` tokens; 0 = auto-size so
    every slot can still reach ``max_seq``, plus the reserved null page).
    ``prefix_cache`` (requires ``paged``) reuses cached prompt blocks
    across requests.  ``draft`` + ``spec_k`` turn on speculative decoding
    (greedy, oracle-token-identical)."""

    def __init__(self, cfg: ArchConfig, mesh, *, slots: int, max_seq: int,
                 hp: Optional[TrainHParams] = None, eos_id: int = 2,
                 prefill_len: Optional[int] = None, decode_micro: int = 0,
                 plan=None, telemetry=None, paged: bool = False,
                 pages: int = 0, page_size: int = 16,
                 prefix_cache: bool = False,
                 draft: Optional[ArchConfig] = None, spec_k: int = 0):
        self.cfg = cfg
        self.mesh = mesh
        self.plan = plan
        if plan is not None:
            from repro.core.axes import deg_total, mesh_info
            plan.validate_for(cfg)
            degs = {d for d in plan.degrees}
            if len(degs) > 1:
                raise ValueError(
                    f"plan {plan.summary()} pins mixed per-layer TMP "
                    f"degrees — the grouped layout is training-only; "
                    f"serve with a uniform-degree plan (e.g. "
                    f"plan(objective='latency').plan)")
            # a pinned uniform degree / pp must MATCH the mesh — silently
            # decoding under a different layout than the plan chose is
            # exactly the scattered-knob failure plans exist to kill
            info = mesh_info(mesh)
            deg = next(iter(degs))
            if deg is not None and deg_total(deg) != info.tp:
                raise ValueError(
                    f"plan {plan.summary()} pins TMP degree {deg} but the "
                    f"mesh's model group is {info.tp}-way — launch with "
                    f"the plan's recorded mesh (serve.py --plan rebuilds "
                    f"it) or a matching --mesh")
            if plan.pp != info.pp:
                raise ValueError(
                    f"plan {plan.summary()} expects pp={plan.pp} but the "
                    f"mesh has pp={info.pp} — launch with the plan's "
                    f"recorded mesh or a matching --pp")
            hp = plan.apply(hp or TrainHParams())
            if decode_micro == 0:
                decode_micro = plan.decode_micro
            if plan.has_seq_layers or plan.seq_shard > 1:
                # ring-attention seq shards are a training/prefill layout;
                # the plan carries them for provenance (checkpoint
                # manifests, relayout) but decode serves head-sharded —
                # surface the degradation instead of silently dropping it
                from repro.obs.recorder import get_recorder
                get_recorder().event(
                    "serving.seq_shard_ignored",
                    f"plan {plan.summary()} carries ring-attention seq "
                    f"shards; decode serves head-sharded (the KV ring "
                    f"spans training sequences, not the decode cache)",
                    seq_shard=plan.seq_shard)
        self.hp = hp or TrainHParams()
        self.slots = slots
        self.max_seq = max_seq
        self.eos_id = eos_id
        if prefill_len is None:
            prefill_len = max(max_seq // 2, 1)
        if not 1 <= prefill_len < max_seq:
            raise ValueError(
                f"prefill_len {prefill_len} must be in [1, max_seq) = "
                f"[1, {max_seq}) — a prompt-full slot needs at least one "
                f"position of decode headroom")
        self.prefill_len = prefill_len

        if prefix_cache and not paged:
            raise ValueError(
                "prefix_cache requires paged=True — prefix reuse maps "
                "cached KV *pages* into the new slot's block table; the "
                "dense per-slot cache has no shareable unit")
        if (spec_k > 0) != (draft is not None):
            raise ValueError(
                "speculative decoding needs both a draft model and "
                "spec_k >= 1 (serve.py --draft <config> --spec-k k); got "
                f"spec_k={spec_k}, draft="
                f"{draft.name if draft is not None else None}")
        if prefix_cache:
            _n, _pat, _tail = prm.stack_layout(cfg)
            other = sorted((set(_pat) | set(_tail)) - {GLOBAL_ATTN})
            if other:
                raise ValueError(
                    f"prefix cache requires an all-global-attention layer "
                    f"pattern; {cfg.name} mixes in {other} — skipping "
                    f"prefill for a shared span cannot reconstruct "
                    f"ring-buffer or recurrent layer states")
        self.spec_k = int(spec_k)
        self.draft_cfg = draft
        if draft is not None and draft.vocab_size != cfg.vocab_size:
            raise ValueError(
                f"draft model {draft.name} has vocab {draft.vocab_size} "
                f"but target {cfg.name} has {cfg.vocab_size} — draft "
                f"proposals must live in the target's token space")

        self.paged: Optional[PagedKVCache] = None
        ptuple = None
        if paged:
            if pages <= 0:
                # auto: every slot can still reach max_seq (paged then
                # costs nothing in capacity and wins it back whenever
                # requests finish early or share prefixes)
                pages = slots * (max_seq // max(page_size, 1)) + 1
            self.paged = PagedKVCache(pages=pages, page_size=page_size,
                                      slots=slots, max_seq=max_seq,
                                      prefix_cache=prefix_cache)
            ptuple = (pages, page_size)

        self.decode_fn, self.specs, self.state_specs = lm.build_decode(
            cfg, mesh, self.hp, global_batch=slots, seq_len=max_seq,
            n_micro=decode_micro, paged=ptuple)
        # donating the KV cache lets XLA alias it through the step on
        # accelerators; the CPU backend ignores donation (and warns), so
        # skip it there
        donate = (1,) if jax.default_backend() != "cpu" else ()
        self.donate_argnums = donate
        self.decode_fn = jax.jit(self.decode_fn, donate_argnums=donate)

        if self.spec_k:
            vf, _, _ = lm.build_verify(
                cfg, mesh, self.hp, global_batch=slots, seq_len=max_seq,
                paged=ptuple)
            self.verify_fn = jax.jit(vf, donate_argnums=donate)
            # the draft serves its own dense cache on the same mesh; its
            # rows are freely rewritten when a rejection rewinds pos
            # (stale rows beyond pos are position-masked, and every
            # revisited position is rewritten before it is attended)
            df, self.draft_specs, self.draft_state_specs = lm.build_decode(
                draft, mesh, self.hp, global_batch=slots, seq_len=max_seq)
            self.draft_fn = jax.jit(df, donate_argnums=donate)
            self.draft_params = None
            self.draft_state = None

        self.params = None
        self.state = None
        self.pos = np.zeros((slots,), np.int32)
        self.cur_tok = np.zeros((slots,), np.int32)
        # token at pos-1 per slot: the speculative catch-up input that
        # repairs the draft cache after a rejected tail
        self.prev_tok = np.zeros((slots,), np.int32)
        self.active: List[Optional[Request]] = [None] * slots
        self.queue: "queue.Queue[Request]" = queue.Queue()
        self._pending: Optional[Request] = None
        self.stats = {"decoded_tokens": 0, "steps": 0, "admitted": 0,
                      "prompt_tokens": 0, "prefix_hits": 0,
                      "prefix_hit_tokens": 0, "spec_proposed": 0,
                      "spec_accepted": 0, "slot_ticks": 0}
        # None -> resolve the process-global recorder per tick, so
        # serve.py's --telemetry (obs.configure) reaches a pre-built engine
        self._telemetry = telemetry

    @property
    def rec(self):
        return (self._telemetry if self._telemetry is not None
                else obs.get_recorder())

    def load(self, seed: int = 0, params=None, draft_params=None):
        self.params = params if params is not None else prm.init_params(
            self.specs, jax.random.PRNGKey(seed))
        self.state = prm.zeros_state(self.state_specs)
        if self.spec_k:
            self.draft_params = (draft_params if draft_params is not None
                                 else prm.init_params(
                                     self.draft_specs,
                                     jax.random.PRNGKey(seed + 1)))
            self.draft_state = prm.zeros_state(self.draft_state_specs)

    @property
    def queued(self) -> int:
        """Requests waiting for a free slot (admission backlog depth,
        including one held back by cache-full backpressure)."""
        return self.queue.qsize() + (self._pending is not None)

    def submit(self, req: Request):
        if len(req.prompt) == 0:
            raise ValueError(f"request {req.rid}: empty prompt")
        if len(req.prompt) > self.prefill_len:
            raise ValueError(
                f"request {req.rid}: prompt of {len(req.prompt)} tokens "
                f"exceeds prefill_len={self.prefill_len} (engine admission "
                f"contract; raise --prefill-len / max_seq or chunk the "
                f"prompt)")
        req.t_submit = time.perf_counter()   # TTFT clock starts here
        self.queue.put(req)

    def _next_request(self) -> Optional[Request]:
        if self._pending is not None:
            req, self._pending = self._pending, None
            return req
        try:
            return self.queue.get_nowait()
        except queue.Empty:
            return None

    def _admit(self):
        for s in range(self.slots):
            if self.active[s] is not None:
                continue
            req = self._next_request()
            if req is None:
                return
            # teacher-forced prompt consumption via decode steps (simple,
            # static-shape admission; a production engine would batch a
            # dedicated prefill_step — see examples/serve_lm.py)
            hit = 0
            if self.paged is not None:
                shared, span = self.paged.lookup(req.prompt)
                # keep at least one prompt token to consume: the engine's
                # first step on the slot must produce a next-token
                hit = min(span, len(req.prompt) - 1)
                if not self.paged.can_admit(
                        len(req.prompt), req.max_new_tokens,
                        shared_pages=len(shared), headroom=self.spec_k):
                    # cache-full backpressure: park the request at the head
                    # of the line until a release frees enough blocks (FIFO
                    # order is preserved — nothing overtakes it)
                    self._pending = req
                    self.rec.counter("serving.admission_deferred", 1)
                    return
                self.paged.admit(s, len(req.prompt), req.max_new_tokens,
                                 headroom=self.spec_k, shared=shared)
                if hit:
                    self.stats["prefix_hits"] += 1
                    self.stats["prefix_hit_tokens"] += hit
            self.active[s] = req
            req.t_admit = time.perf_counter()
            self.pos[s] = hit
            self.cur_tok[s] = int(req.prompt[hit])
            self.prev_tok[s] = int(req.prompt[max(hit - 1, 0)])
            req._prompt_cursor = hit + 1
            req._inserted = False
            self.stats["admitted"] += 1
            self.stats["prompt_tokens"] += len(req.prompt)

    # ------------------------------------------------------------------
    # paged plumbing
    # ------------------------------------------------------------------
    def _paged_args(self, cow: List[Tuple[int, int]]):
        """Device-ready (tables, cow_src, cow_dst): the cow list is padded
        to a fixed length with (0, 0) pairs (copying the null page onto
        itself is a no-op), so the jitted step never recompiles."""
        if len(cow) > self.slots:
            raise RuntimeError(
                f"{len(cow)} COW copies in one step exceeds the padded "
                f"capacity of {self.slots} — at most one shared block can "
                f"enter a slot's write range per step")
        src = np.zeros((self.slots,), np.int32)
        dst = np.zeros((self.slots,), np.int32)
        for i, (a, b) in enumerate(cow):
            src[i], dst[i] = a, b
        return (jnp.asarray(self.paged.table), jnp.asarray(src),
                jnp.asarray(dst))

    def _maybe_insert_prefix(self, s: int):
        """Index the slot's prompt blocks once the full prompt is written
        (before any release, so the pages outlive the slot)."""
        req = self.active[s]
        if (self.paged is None or not self.paged.prefix_enabled
                or req is None or req._inserted
                or self.pos[s] < len(req.prompt)):
            return
        self.paged.insert(s, req.prompt)
        req._inserted = True

    def _release_slot(self, s: int):
        self.active[s] = None
        if self.paged is not None:
            self.paged.release(s)
            self.paged.check()
            self._check_invariants()

    def _check_invariants(self):
        """Engine-level reconciliation on top of ``PagedKVCache.check()``:
        released slots map nothing, and every non-null page is either
        free or held (slot tables / prefix index) — no leaked limbo."""
        pc = self.paged
        for s in range(self.slots):
            if self.active[s] is None and pc.mapped(s):
                raise RuntimeError(
                    f"slot {s} is free but still maps {pc.mapped(s)} "
                    f"pages — release leaked blocks")
        held = {int(pg) for srow in pc.table for pg in srow if pg}
        held |= {e.page for e in pc._index.values()}
        if len(held) + pc.free_pages != pc.pages - 1:
            raise RuntimeError(
                f"page conservation violated: {len(held)} held + "
                f"{pc.free_pages} free != {pc.pages - 1} allocatable")

    # ------------------------------------------------------------------
    # stepping
    # ------------------------------------------------------------------
    def step(self):
        """One engine iteration: admit, then decode one token for all
        slots (or one speculative round of up to ``spec_k + 1``).

        Each phase of the tick is a profiler annotation (``serve.admit``,
        ``serve.prepare``, ``serve.dispatch``, ``serve.sync``,
        ``serve.emit``; ``serve.spec_draft`` and ``serve.spec_verify`` for
        a speculative round), so a trace can say what the host was doing
        whenever the chip waits."""
        rec = self.rec
        with obs.trace_annotation("serve.admit"):
            self._admit()
            active = sum(a is not None for a in self.active)
            self.stats["slot_ticks"] += active
            rec.gauge("serving.queue_depth", self.queued)
            rec.gauge("serving.slot_occupancy", active / self.slots)
            if self.paged is not None:
                rec.gauge("serving.free_pages", self.paged.free_pages)
                if self.paged.prefix_enabled and self.stats["prompt_tokens"]:
                    rec.gauge("serving.prefix_hit_rate",
                              self.stats["prefix_hit_tokens"]
                              / self.stats["prompt_tokens"])
        if self.spec_k:
            if self.stats["spec_proposed"]:
                rec.gauge("serving.spec_accept_rate",
                          self.stats["spec_accepted"]
                          / self.stats["spec_proposed"])
            self._spec_step(rec)
        else:
            self._plain_step(rec)

    def _plain_step(self, rec):
        t0 = time.perf_counter()
        with obs.trace_annotation("serve.prepare"):
            tokens = jnp.asarray(self.cur_tok)
            pos = jnp.asarray(self.pos)
            extra = ()
            if self.paged is not None:
                cow: List[Tuple[int, int]] = []
                for s in range(self.slots):
                    if self.active[s] is not None:
                        cow += self.paged.ensure_writable(
                            s, int(self.pos[s]), int(self.pos[s]))
                extra = self._paged_args(cow)
        with obs.trace_annotation("serve.dispatch"):
            next_tok, self.state = self.decode_fn(self.params, self.state,
                                                  tokens, pos, *extra)
        with obs.trace_annotation("serve.sync"):
            next_tok = np.asarray(jax.device_get(next_tok))
        now = time.perf_counter()
        rec.observe("serving.decode_step_s", now - t0)
        self.stats["steps"] += 1
        with obs.trace_annotation("serve.emit"):
            self._emit_plain(rec, next_tok, now)

    def _first_token(self, rec, req: Request, now: float):
        req.t_first = now
        if req.t_submit is not None:
            rec.observe("serving.ttft_s", req.t_first - req.t_submit,
                        rid=req.rid)

    def _emit_plain(self, rec, next_tok: np.ndarray, now: float):
        decoded = 0
        for s in range(self.slots):
            req = self.active[s]
            if req is None:
                continue
            self.pos[s] += 1
            self.prev_tok[s] = self.cur_tok[s]
            self._maybe_insert_prefix(s)
            cur = getattr(req, "_prompt_cursor", len(req.prompt))
            if cur < len(req.prompt):       # still consuming the prompt
                self.cur_tok[s] = int(req.prompt[cur])
                req._prompt_cursor = cur + 1
                continue
            tok = int(next_tok[s])
            if not req.out_tokens:
                self._first_token(rec, req, now)
            req.out_tokens.append(tok)
            self.stats["decoded_tokens"] += 1
            decoded += 1
            self.cur_tok[s] = tok
            if (tok == self.eos_id
                    or len(req.out_tokens) >= req.max_new_tokens
                    or self.pos[s] >= self.max_seq - 1):
                req.done = True
                self._release_slot(s)
        if decoded:
            rec.counter("serving.decoded_tokens", decoded)

    # ------------------------------------------------------------------
    # speculative decoding
    # ------------------------------------------------------------------
    def _spec_step(self, rec):
        """One speculative round: k+1 draft forwards (one catch-up plus k
        proposals), one batched verify, host-side longest-agreeing-run
        acceptance.  Greedy-token-identical to undrafted decode."""
        k = self.spec_k
        t0 = time.perf_counter()
        pos0 = self.pos.copy()
        # tok_block[:, j] is the token at absolute position pos0 + j;
        # column 0 is cur_tok, prompt positions are teacher-forced over
        # whatever the draft proposes
        tok_block = np.zeros((self.slots, k + 1), np.int32)
        tok_block[:, 0] = self.cur_tok

        def forced(s: int, j: int) -> Optional[int]:
            req = self.active[s]
            p = int(pos0[s]) + j
            if req is not None and p < len(req.prompt):
                return int(req.prompt[p])
            return None

        with obs.trace_annotation("serve.spec_draft"):
            # catch-up: re-consume prev_tok at pos-1 so the draft cache
            # row the last rejection left stale is repaired before the
            # draft attends through it; its output (a prediction for the
            # already-known cur_tok) is discarded
            d_tok = jnp.asarray(self.prev_tok)
            d_pos = jnp.asarray(np.maximum(pos0 - 1, 0))
            _, self.draft_state = self.draft_fn(
                self.draft_params, self.draft_state, d_tok, d_pos)
            for j in range(1, k + 1):
                d_tok = jnp.asarray(tok_block[:, j - 1])
                d_pos = jnp.asarray(
                    np.minimum(pos0 + (j - 1), self.max_seq - 1))
                nt, self.draft_state = self.draft_fn(
                    self.draft_params, self.draft_state, d_tok, d_pos)
                prop = np.asarray(jax.device_get(nt))
                for s in range(self.slots):
                    f = forced(s, j)
                    tok_block[s, j] = int(prop[s]) if f is None else f

        extra = ()
        if self.paged is not None:
            cow: List[Tuple[int, int]] = []
            for s in range(self.slots):
                if self.active[s] is not None:
                    cow += self.paged.ensure_writable(
                        s, int(pos0[s]), int(pos0[s]) + k)
            extra = self._paged_args(cow)
        with obs.trace_annotation("serve.spec_verify"):
            choices, self.state = self.verify_fn(
                self.params, self.state, jnp.asarray(tok_block),
                jnp.asarray(pos0), *extra)
            choices = np.asarray(jax.device_get(choices))
        now = time.perf_counter()
        rec.observe("serving.decode_step_s", now - t0)
        self.stats["steps"] += 1

        decoded = 0
        for s in range(self.slots):
            req = self.active[s]
            if req is None:
                continue
            plen = len(req.prompt)
            self.stats["spec_proposed"] += sum(
                1 for j in range(1, k + 1) if int(pos0[s]) + j >= plen)
            j = 0
            stop = False
            nxt_pos = int(pos0[s]) + 1
            nxt_tok = int(tok_block[s, 0])
            while True:
                # consume tok_block[s, j] at position pos0+j; the token at
                # pos0+j+1 is either the next forced prompt token or the
                # verifier's (== the undrafted oracle's) emission
                nxt_pos = int(pos0[s]) + j + 1
                if nxt_pos < plen:
                    nxt_tok = int(req.prompt[nxt_pos])
                    req._prompt_cursor = nxt_pos + 1
                else:
                    nxt_tok = int(choices[s, j])
                    if not req.out_tokens:
                        self._first_token(rec, req, now)
                    req.out_tokens.append(nxt_tok)
                    self.stats["decoded_tokens"] += 1
                    decoded += 1
                    if (nxt_tok == self.eos_id
                            or len(req.out_tokens) >= req.max_new_tokens):
                        stop = True
                if nxt_pos >= self.max_seq - 1:
                    stop = True
                if stop or j >= k:
                    break
                if int(tok_block[s, j + 1]) != nxt_tok:
                    break   # divergence — nxt_tok is the oracle correction
                if nxt_pos >= plen:
                    self.stats["spec_accepted"] += 1   # a proposal survived
                j += 1
            self.pos[s] = nxt_pos
            self.cur_tok[s] = nxt_tok
            self.prev_tok[s] = int(tok_block[s, j])
            self._maybe_insert_prefix(s)
            if stop:
                req.done = True
                self._release_slot(s)
        if decoded:
            rec.counter("serving.decoded_tokens", decoded)

    def run_until_drained(self, max_steps: int = 10_000) -> Dict:
        t0 = time.perf_counter()
        for _ in range(max_steps):
            if (self.queue.empty() and self._pending is None
                    and all(a is None for a in self.active)):
                break
            self.step()
        dt = time.perf_counter() - t0
        rec = self.rec
        rec.gauge("serving.drain_s", dt)
        rec.gauge("serving.tok_per_s",
                  self.stats["decoded_tokens"] / max(dt, 1e-9))
        out = {**self.stats, "wall_s": dt,
               "tok_per_s": self.stats["decoded_tokens"] / max(dt, 1e-9)}
        if self.paged is not None:
            self.paged.check()
            self._check_invariants()
            out["paged"] = dict(self.paged.stats,
                                free_pages=self.paged.free_pages,
                                index_size=self.paged.index_size)
            if self.paged.prefix_enabled:
                hit = (self.stats["prefix_hit_tokens"]
                       / max(self.stats["prompt_tokens"], 1))
                rec.gauge("serving.prefix_hit_rate", hit)
                out["prefix_hit_rate"] = hit
        if self.spec_k:
            acc = (self.stats["spec_accepted"]
                   / max(self.stats["spec_proposed"], 1))
            rec.gauge("serving.spec_accept_rate", acc)
            out["spec_accept_rate"] = acc
        return out
