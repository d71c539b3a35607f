"""Capture a jax.profiler trace of engine.train_batch on the real chip.

Usage:  python tools/profile_step.py [model] [batch] [seq] [steps]
Writes a TensorBoard-loadable trace under <repo>/profile_out/ and prints
the top-level step timing. The trace shows per-op device time (MXU vs VPU
vs HBM stalls) — the ground truth for the bench tuning loop: profile
before tuning.
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2Model, gpt2_config
from deepspeed_tpu.utils.compile_cache import enable_compile_cache

MODEL = sys.argv[1] if len(sys.argv) > 1 else "gpt2-350m"
BS = int(sys.argv[2]) if len(sys.argv) > 2 else 48
SEQ = int(sys.argv[3]) if len(sys.argv) > 3 else 1024
STEPS = int(sys.argv[4]) if len(sys.argv) > 4 else 5
OUT = os.path.normpath(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                 "profile_out"))


def main():
    enable_compile_cache()
    cfg = gpt2_config(MODEL, n_positions=SEQ, dtype=jnp.bfloat16,
                      remat=True, scan_layers=True)
    model = GPT2Model(cfg)
    n_dev = len(jax.devices())
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config_params={
        "train_batch_size": BS * n_dev,
        "train_micro_batch_size_per_gpu": BS,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 2},
        "mesh": {"data": n_dev, "model": 1, "pipe": 1},
        "steps_per_print": 10 ** 9,
    })
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (1, BS * n_dev, SEQ))
    batch = {"input_ids": ids, "labels": ids.copy()}

    # compile + warm
    loss = engine.train_batch(batch=batch)
    float(jax.device_get(loss))
    t0 = time.time()
    loss = engine.train_batch(batch=batch)
    float(jax.device_get(loss))
    print(f"warm step: {(time.time()-t0)*1000:.1f} ms")

    os.makedirs(OUT, exist_ok=True)
    with jax.profiler.trace(OUT):
        for _ in range(STEPS):
            loss = engine.train_batch(batch=batch)
        float(jax.device_get(loss))
    print(f"trace written to {OUT} — load with "
          f"tensorboard --logdir {OUT} (profile plugin)")


if __name__ == "__main__":
    main()
