"""Train a ~100M-parameter dense LM on the synthetic pipeline (CPU).

Exercises the full training substrate: data pipeline -> jit'd train step
(remat + AdamW) -> checkpointing.  ~100M params; a few hundred steps with
--steps 300 (default 60 keeps CI-speed).

Run:  PYTHONPATH=src python examples/train_lm.py [--steps 300]
"""

import argparse

from repro.core.compile_cache import use_compile_cache
from repro.data import DataConfig
from repro.models.base import ArchConfig
from repro.train import TrainConfig, train

ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=60)
ap.add_argument("--batch", type=int, default=4)
ap.add_argument("--seq", type=int, default=256)
ap.add_argument("--checkpoint-dir", default="/tmp/repro_lm_ckpt")
args = ap.parse_args()
use_compile_cache()

cfg = ArchConfig(
    name="lm-100m", arch_type="dense",
    n_layers=8, d_model=512, n_heads=8, n_kv_heads=4,
    d_ff=2048, vocab=32000,
    citation="example config (~100M params)",
)
print(f"params: {cfg.param_count()/1e6:.0f}M")
out = train(
    cfg,
    DataConfig(batch_size=args.batch, seq_len=args.seq),
    TrainConfig(steps=args.steps, log_every=10, checkpoint_every=50,
                checkpoint_dir=args.checkpoint_dir),
)
print(f"loss: {out['losses'][0]:.3f} -> {out['losses'][-1]:.3f}")
