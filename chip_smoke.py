"""Bring-up smoke on a TPU: train and serve at full width through the same
entry points ``launch/train.py`` and ``launch/serve.py`` use
(``resolve_launch``, ``Trainer``, ``ServingEngine``).

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # one host with four chips

One chip: phase 1 trains the paper's gpt-h1024 (every field as published,
bf16) on a 1x1 mesh under ``oases`` at seq 1024, global batch 8; phase 2
serves internlm2-1.8b at full width and depth.  Four chips: gpt-h2048 at
full size on a 1x4 TMP mesh under ``megatron``, ``oases`` and ``fused``,
and nothing else.  Weights are random, made from ``--seed``.

Everything runs in this one process.  It fails before any work when JAX
finds no TPU, and any failed check ends the run with a non-zero exit.  The
last line of stdout, printed only after every phase passed, is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Numbers printed on the way are one-off readings, not benchmark results.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

TRAIN_BATCH = 8
SERVE_REQUESTS = 4
SERVE_NEW_TOKENS = 16
# |loss - megatron's loss| bounds of the four-chip phase, in bf16
FIRST_LOSS_TOL = 1e-2
LAST_LOSS_TOL = 5e-2


def require_tpu(count: int) -> dict:
    import jax
    if jax.default_backend() != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX's default backend "
                         f"is {jax.default_backend()!r}")
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"device {json.dumps(device)}", flush=True)
    if len(devs) != count:
        raise SystemExit(f"chip_smoke: this phase needs {count} chip(s), "
                         f"JAX sees {len(devs)}")
    return device


class CompileLog:
    """Seconds JAX spent compiling (or loading from the persistent cache)
    and the cache's hits and misses, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def line(self) -> str:
        return (f"backend compile {self.seconds} s, persistent cache "
                f"{self.hits} hits / {self.misses} misses")


def train(cfg, hp, *, mesh: str, batch: int, seq: int, steps: int,
          seed: int = 0, checkpoint: bool = True) -> dict:
    """Train ``steps`` steps on a ``Trainer`` built as ``launch/train.py``
    builds it, with the step compiled up front so its compile time is read
    apart from the steady steps.

    With ``checkpoint`` the steps run through ``Trainer.train``, whose
    checkpoint at the last step goes to a temporary directory deleted
    here.  Without it they run the trainer's own jitted step on its own
    data path, and nothing is written."""
    import itertools

    import jax

    from repro.data.pipeline import DataConfig, Prefetcher
    from repro.launch.mesh import resolve_launch
    from repro.runtime import Trainer

    m, plan, hp = resolve_launch(cfg, hp, mesh=mesh)
    ckpt_dir = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
    try:
        trainer = Trainer(cfg, m, hp, global_batch=batch, seq_len=seq,
                          ckpt_dir=ckpt_dir, plan=plan,
                          log_fn=lambda s: print(s, flush=True))
        params, opt, _ = trainer.init_state(seed)
        wq = params["blocks"][0]["wq"]     # column-parallel under TMP
        wq_shards = [(sh.device.id, tuple(sh.data.shape))
                     for sh in wq.addressable_shards]
        dcfg = DataConfig(global_batch=batch, seq_len=seq,
                          vocab_size=cfg.vocab_size,
                          microbatch=trainer.hp.microbatch)
        data = Prefetcher(dcfg, m)
        try:
            _, first_batch = next(data)
        finally:
            data.close()
        t0 = time.perf_counter()
        compiled = trainer.step_fn.lower(params, opt, first_batch).compile()
        compile_s = time.perf_counter() - t0
        if checkpoint:
            losses = trainer.train(steps, ckpt_every=steps, seed=seed,
                                   state=(params, opt, 0))["losses"]
            step_s = list(trainer.rec.hists["trainer.step_time_s"])
        else:
            losses, step_s = [], []
            data = Prefetcher(dcfg, m)
            try:
                for _, b in itertools.islice(data, steps):
                    t0 = time.perf_counter()
                    params, opt, metrics = trainer.step_fn(params, opt, b)
                    losses.append(float(metrics["loss"]))
                    step_s.append(time.perf_counter() - t0)
            finally:
                data.close()
        stats = jax.devices()[0].memory_stats() or {}
        out = {
            "losses": losses,
            "compile_s": compile_s,
            "first_step_ms": step_s[0] * 1e3,
            "median_step_ms": statistics.median(step_s[1:]) * 1e3,
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "hlo": compiled.as_text(),
            "wq_shape": tuple(wq.shape),
            "wq_shards": wq_shards,
        }
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    # the trainer sits in a reference cycle (its checkpointer's save hook
    # refers back to it) and holds the live state: free the device memory
    # now, before the next phase allocates its own
    del trainer, params, opt, first_batch
    gc.collect()
    return out


def check_losses(losses, steps: int):
    if len(losses) != steps:
        raise AssertionError(f"{len(losses)} losses for {steps} steps")
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")


def serve(cfg, *, seed: int = 0) -> dict:
    """Serve ``SERVE_REQUESTS`` requests through ``ServingEngine`` as
    ``launch/serve.py`` does; EOS is switched off so every request must
    decode its full token budget."""
    import numpy as np

    from repro.configs.base import TrainHParams
    from repro.launch.mesh import resolve_launch
    from repro.serving import Request, ServingEngine

    m, plan, hp = resolve_launch(cfg, TrainHParams(), mesh="1x1")
    eng = ServingEngine(cfg, m, slots=SERVE_REQUESTS, max_seq=64, hp=hp,
                        plan=plan, eos_id=-1)
    eng.load(seed=seed)
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i,
                    prompt=rng.integers(3, cfg.vocab_size,
                                        int(rng.integers(16, 33)),
                                        dtype=np.int32),
                    max_new_tokens=SERVE_NEW_TOKENS)
            for i in range(SERVE_REQUESTS)]
    for r in reqs:
        eng.submit(r)
    stats = eng.run_until_drained()
    for r in reqs:
        if not r.done or len(r.out_tokens) != SERVE_NEW_TOKENS:
            raise AssertionError(
                f"request {r.rid}: done={r.done}, {len(r.out_tokens)} of "
                f"{SERVE_NEW_TOKENS} tokens")
        if not all(0 <= t < cfg.vocab_size for t in r.out_tokens):
            raise AssertionError(f"request {r.rid}: token out of vocab: "
                                 f"{r.out_tokens}")
    return {"requests": len(reqs),
            "prompt_tokens": stats["prompt_tokens"],
            "decoded_tokens": stats["decoded_tokens"],
            "engine_steps": stats["steps"], "wall_s": stats["wall_s"],
            "sample_output": reqs[0].out_tokens[:8]}


def one_chip(seed: int):
    from repro.configs.base import TrainHParams
    from repro.configs.gpt_oases import PAPER_SEQ_LEN
    from repro.configs.registry import get_config

    steps = 6
    # the hyper-parameters launch/train.py derives for a run this long
    hp = TrainHParams(schedule="oases", total_steps=steps,
                      warmup_steps=max(steps // 20, 1))
    r = train(get_config("gpt-h1024"), hp, mesh="1x1", batch=TRAIN_BATCH,
              seq=PAPER_SEQ_LEN, steps=steps, seed=seed)
    print("phase 1 train gpt-h1024 " + json.dumps({
        "mesh": "1x1", "schedule": "oases", "batch": TRAIN_BATCH,
        "seq": PAPER_SEQ_LEN, "steps": steps,
        "compile_s": r["compile_s"],
        "first_step_ms": r["first_step_ms"],
        "median_step_ms": r["median_step_ms"],
        "peak_bytes_in_use": r["peak_bytes_in_use"],
        "first_loss": r["losses"][0], "last_loss": r["losses"][-1],
        "losses": r["losses"]}), flush=True)
    check_losses(r["losses"], steps)
    del r

    s = serve(get_config("internlm2-1.8b"), seed=seed)
    print("phase 2 serve internlm2-1.8b " + json.dumps(s), flush=True)


def four_chips(seed: int):
    from repro.configs.base import TrainHParams
    from repro.configs.gpt_oases import PAPER_SEQ_LEN
    from repro.configs.registry import get_config

    cfg = get_config("gpt-h2048")
    steps = 4
    runs = {}
    for sched in ("megatron", "oases", "fused"):
        # warm up over the whole run: at the full 3e-4 from the first
        # update this 1.8B model's loss went 11.2 -> 20.8 on the chip
        hp = TrainHParams(schedule=sched, learning_rate=1e-4,
                          total_steps=steps, warmup_steps=steps)
        # no checkpoint: writing 25 GB of state three times would cost
        # more chip time than the training
        r = train(cfg, hp, mesh="1x4", batch=TRAIN_BATCH,
                  seq=PAPER_SEQ_LEN, steps=steps, seed=seed,
                  checkpoint=False)
        shards = r["wq_shards"]
        permute = "collective-permute" in r["hlo"]
        print(f"four-chip train gpt-h2048 {sched} " + json.dumps({
            "mesh": "1x4", "batch": TRAIN_BATCH, "seq": PAPER_SEQ_LEN,
            "steps": steps, "compile_s": r["compile_s"],
            "median_step_ms": r["median_step_ms"],
            "peak_bytes_in_use_dev0": r["peak_bytes_in_use"],
            "losses": r["losses"], "collective_permute": permute,
            "wq": {"shape": r["wq_shape"], "shards": shards}}),
            flush=True)
        check_losses(r["losses"], steps)
        size = math.prod(r["wq_shape"])
        if len({d for d, _ in shards}) != 4 or \
                any(4 * math.prod(shape) != size for _, shape in shards):
            raise AssertionError(f"{sched}: wq {r['wq_shape']} is not split "
                                 f"in four over four devices: {shards}")
        if sched == "fused" and not permute:
            raise AssertionError("fused: no collective-permute in the "
                                 "compiled step; the ring was not taken")
        runs[sched] = r["losses"]
    ref = runs["megatron"]
    for sched, losses in runs.items():
        d0, d1 = abs(losses[0] - ref[0]), abs(losses[-1] - ref[-1])
        print(f"{sched} vs megatron: |d first| {d0:.3g}, |d last| {d1:.3g}",
              flush=True)
        if d0 > FIRST_LOSS_TOL or d1 > LAST_LOSS_TOL:
            raise AssertionError(
                f"{sched} losses {losses} differ from megatron's {ref}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip TMP phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    device = require_tpu(4 if args.four_chips else 1)
    from repro.launch.cache import enable_compile_cache
    print(f"compile cache {enable_compile_cache()}", flush=True)
    compiles = CompileLog()
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    print(f"all phases passed in {time.perf_counter() - t0} s; "
          f"{compiles.line()}", flush=True)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
