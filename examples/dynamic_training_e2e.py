"""End-to-end driver: train the paper's ~110M-parameter Bert-base trunk
for a few hundred steps under a memory budget, with dynamic input sizes.

This is the full-size counterpart of quickstart.py — the exact model the
paper evaluates (12 encoders, d=768, 110M params).  On this CPU container
a step takes a few seconds; pass --steps to shorten.

    PYTHONPATH=src python examples/dynamic_training_e2e.py --steps 200
"""
import argparse
import time

import jax
import numpy as np

from repro.core import MimosePlanner
from repro.data.pipeline import make_batches
from repro.models.lm import build_model
from repro.models.registry import get_config
from repro.optim.adamw import AdamW, cosine_schedule
from repro.train import checkpoint as ckpt
from repro.train.trainer import Trainer

ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=200)
ap.add_argument("--batch-size", type=int, default=4)
ap.add_argument("--budget-gb", type=float, default=2.5)
ap.add_argument("--save", default="/tmp/bert_base_mimose.msgpack")
args = ap.parse_args()

cfg = get_config("bert_base_paper")          # full 110M config
lm = build_model(cfg)
params = lm.init(jax.random.PRNGKey(0))
n = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(params))
print(f"training {cfg.name}: {n / 1e6:.0f}M params, "
      f"{cfg.num_layers} encoders, budget {args.budget_gb} GB")

planner = MimosePlanner(lm, args.budget_gb * 2**30, warmup_samples=3,
                        quantum=64)
opt = AdamW(lr=cosine_schedule(1e-4, 20, args.steps))
trainer = Trainer(lm, planner, opt)
opt_state = opt.init(params)

t0 = time.time()
for i, batch in enumerate(make_batches(
        "qqp", batch_size=args.batch_size, vocab_size=cfg.vocab_size,
        num_batches=args.steps, quantum=64, seed=0)):
    params, opt_state, _ = trainer.step(params, opt_state, batch)
    if i % 10 == 0:
        st = trainer.history[-1]
        loss = st.loss    # reads the loss, and with it the step time
        print(f"step {i:4d}  loss {loss:7.4f}  S={batch['tokens'].shape[1]:4d}"
              f"  remat {st.remat_units:2d}/12  {st.step_time_s:6.2f}s"
              f"  plan {1e3 * st.plan_time_s:7.2f}ms")

print(f"\n{args.steps} steps in {(time.time() - t0) / 60:.1f} min")
print("summary:", trainer.summary())
print("planner:", planner.stats)
ckpt.save(args.save, params)
print("checkpoint written to", args.save)
