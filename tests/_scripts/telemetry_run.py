"""Subprocess body: telemetry end-to-end on a real TMP mesh — a short
training run with a JSONL sink must produce a schema-valid trace carrying
step-time histograms and per-host heartbeat metrics, and each step must
show on a profiler capture as a ``train.step`` host span."""
import runner  # noqa: F401  (must be first: sets XLA_FLAGS before jax)

import glob
import json
import os
import tempfile

import jax
from jax.profiler import ProfileData

from repro import obs
from repro.configs.base import TrainHParams
from repro.obs.schema import SchemaError, validate_lines
from repro.runtime import Trainer
from repro.runtime import elastic as el

mesh = runner.mesh(2, 4)
cfg = runner.reduced_config("internlm2-1.8b")
ckpt = tempfile.mkdtemp()
tel = tempfile.mkdtemp()

logs = []
rec = obs.Recorder(tel, flush_every=1, console=logs.append)
trainer = Trainer(cfg, mesh,
                  TrainHParams(schedule="oases", total_steps=8,
                               warmup_steps=2, learning_rate=1e-3),
                  global_batch=8, seq_len=64, ckpt_dir=ckpt,
                  telemetry=rec, host_id=1)
prof = tempfile.mkdtemp()
jax.profiler.start_trace(prof)
try:
    res = trainer.train(8, ckpt_every=4)
finally:
    jax.profiler.stop_trace()
rec.close()

# ---- schema-valid JSONL trace --------------------------------------------
lines = open(os.path.join(tel, "telemetry.jsonl")).read().splitlines()
try:
    recs = validate_lines(lines)
    runner.report("telemetry-schema", len(recs) >= 8,
                  f"{len(recs)} records, all valid")
except SchemaError as e:
    runner.report("telemetry-schema", False, str(e))
    recs = []

names = [r["name"] for r in recs]

# ---- trainer metrics -----------------------------------------------------
steps = [r for r in recs if r["name"] == "trainer.step_time_s"]
runner.report("telemetry-step-hist",
              len(steps) == 8 and all(r["kind"] == "histogram"
                                      and r["value"] > 0 for r in steps),
              f"{len(steps)} step samples")
runner.report("telemetry-ckpt-latency",
              any(r["name"] == "trainer.ckpt_write_s" for r in recs),
              "async checkpoint write latency recorded")
runner.report("telemetry-console",
              any("loss" in ln for ln in logs),
              f"{len(logs)} console lines preserved")

# ---- host spans on the profiler's clock --------------------------------
pb = glob.glob(os.path.join(prof, "**", "*.xplane.pb"), recursive=True)
spans = [e for p in ProfileData.from_file(pb[0]).planes
         if p.name.startswith("/host:") for ln in p.lines
         for e in ln.events if e.name == "train.step"] if pb else []
runner.report("telemetry-step-spans", len(spans) == 8,
              f"{len(spans)} train.step spans in the profile")

# ---- enriched heartbeat (straggler localization input) -------------------
hb = el.read_heartbeat(el.heartbeat_path(ckpt))
runner.report("telemetry-heartbeat",
              hb is not None and hb.get("host") == 1
              and isinstance(hb.get("step_time_ewma_s"), float)
              and hb.get("step") == 7,
              json.dumps(hb))

runner.report("telemetry-run-complete",
              res["final_step"] >= 8 and len(res["losses"]) == 8,
              f"final_step={res['final_step']}")
