"""Sweep flash-attention block sizes on the real chip; checks numerics vs the
jnp reference path at each config.

--chain N (5th positional arg) wraps N sequential attention calls in ONE jit
so per-dispatch overhead doesn't swamp the kernel time —
representative of 24 layers inside a fused train step."""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.transformer.flash_attention import flash_attention
from deepspeed_tpu.ops.transformer.functional import (
    scaled_dot_product_attention)

BS = int(sys.argv[1]) if len(sys.argv) > 1 else 8
H = int(sys.argv[2]) if len(sys.argv) > 2 else 16
SEQ = int(sys.argv[3]) if len(sys.argv) > 3 else 1024
D = int(sys.argv[4]) if len(sys.argv) > 4 else 64
CHAIN = int(sys.argv[5]) if len(sys.argv) > 5 else 1
ITERS = 20


def bench(att_fn, *args, flops):
    def chained(q, k, v):
        y = q
        for _ in range(CHAIN):
            y = att_fn(y, k, v)
        return y

    fn = jax.jit(chained)
    flops = flops * CHAIN
    o = fn(*args)
    jax.block_until_ready(o)
    jax.device_get(jax.tree_util.tree_leaves(o)[0].ravel()[0])
    t0 = time.time()
    for _ in range(ITERS):
        o = fn(*args)
    jax.device_get(jax.tree_util.tree_leaves(o)[0].ravel()[0])
    # per-CALL time: the chain amortizes dispatch, the report stays
    # comparable with --chain 1 runs
    dt = (time.time() - t0) / ITERS / CHAIN
    return dt, (flops / CHAIN) / dt / 1e12


def main():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((BS, H, SEQ, D)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((BS, H, SEQ, D)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((BS, H, SEQ, D)), jnp.bfloat16)
    att_flops = 4.0 * BS * H * SEQ * SEQ * D

    ref = jax.jit(lambda q, k, v: scaled_dot_product_attention(
        q, k, v, causal=True, use_pallas=False))
    ref_out = ref(q, k, v)
    dt, tf = bench(ref, q, k, v, flops=att_flops)
    print(f"{'jnp ref fwd':28s} {dt*1000:8.2f} ms {tf:6.1f} TF", flush=True)
    refg = jax.jit(jax.grad(lambda q, k, v: scaled_dot_product_attention(
        q, k, v, causal=True, use_pallas=False).astype(jnp.float32).sum()))
    dt, tf = bench(refg, q, k, v, flops=3.5*att_flops)
    print(f"{'jnp ref fwd+bwd':28s} {dt*1000:8.2f} ms {tf:6.1f} TF", flush=True)

    for bq, bk in [(128, 128), (256, 256), (256, 512), (512, 512),
                   (256, 1024), (512, 1024), (1024, 1024)]:
        if bq > SEQ or bk > SEQ:
            continue
        f = jax.jit(lambda q, k, v, bq=bq, bk=bk: flash_attention(
            q, k, v, causal=True, block_q=bq, block_k=bk))
        try:
            out = f(q, k, v)
            err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                        - ref_out.astype(jnp.float32))))
            dt, tf = bench(f, q, k, v, flops=att_flops)
            g = jax.jit(jax.grad(lambda q, k, v, bq=bq, bk=bk: flash_attention(
                q, k, v, causal=True, block_q=bq, block_k=bk)
                .astype(jnp.float32).sum()))
            dtg, tfg = bench(g, q, k, v, flops=3.5*att_flops)
            print(f"pallas bq={bq:4d} bk={bk:4d}  fwd {dt*1000:7.2f} ms "
                  f"{tf:6.1f} TF  fwd+bwd {dtg*1000:7.2f} ms {tfg:6.1f} TF  "
                  f"maxerr {err:.3e}", flush=True)
        except Exception as e:
            print(f"pallas bq={bq:4d} bk={bk:4d}  FAILED: "
                  f"{type(e).__name__}: {str(e)[:120]}", flush=True)


if __name__ == "__main__":
    main()
