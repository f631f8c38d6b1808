"""End-to-end driver: fine-tune a ~100M-param LLM with D2FT for a few
hundred steps on synthetic Markov data, on the masked or the kernel
execution path.

  PYTHONPATH=src python examples/d2ft_llm_finetune.py [--steps 200]
"""
import argparse
import time

import jax
import numpy as np

from repro.configs.base import D2FTConfig, ModelConfig
from repro.data.synthetic import lm_batches
from repro.models.transformer import init_model
from repro.optim.optimizers import adamw
from repro.train.loop import finetune

ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=200)
ap.add_argument("--kernel", action="store_true",
                help="route attention through the Pallas gated flash kernel "
                     "(gate-aware backward; interpret mode on CPU)")
args = ap.parse_args()

# ~100M params: 12 layers, d_model 768 (GPT-2-small-ish)
cfg = ModelConfig(name="llm100m", arch_type="dense", n_layers=12,
                  d_model=768, n_heads=12, n_kv_heads=12, d_ff=3072,
                  vocab_size=8192)
params = init_model(jax.random.PRNGKey(0), cfg)
n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
print(f"model: {n_params/1e6:.1f}M params")

d2 = D2FTConfig(n_microbatches=4, n_pf=2, n_po=1, head_groups=12)
print(f"D2FT budget: compute {(2 + 0.4) / 4:.0%}, comm {(2 + 0.5) / 4:.0%}")

batches = lm_batches(0, cfg.vocab_size, batch=8, seq=128, steps=args.steps)
t0 = time.time()
params, _, log = finetune(params, cfg, d2, adamw(3e-4), batches,
                          steps=args.steps, use_kernel=args.kernel)
path = "kernel" if args.kernel else "masked"
print(f"{args.steps} steps ({path} path) in {time.time()-t0:.0f}s")
print(f"loss: {np.mean(log.losses[:10]):.3f} -> "
      f"{np.mean(log.losses[-10:]):.3f}")
