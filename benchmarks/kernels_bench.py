"""Micro-benchmarks of the kernel reference paths (CPU). On TPU the Pallas
paths replace the ref ops; wall-times here are CPU sanity numbers."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels import ref


def _time(fn, *args, reps=5):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        fn(*args).block_until_ready()
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
        (out[0] if isinstance(out, tuple) else out).block_until_ready()
    return (time.time() - t0) / reps * 1e6


def kernel_microbench():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (512, 2048))
    sc = jnp.zeros((2048,))
    f = jax.jit(lambda a, b: ref.rmsnorm_ref(a, b))
    print(f"kern.rmsnorm.512x2048,{_time(f, x, sc):.0f},ref_cpu", flush=True)

    w = jax.random.normal(jax.random.fold_in(key, 4), (1 << 20,))
    buf = jnp.zeros((1 << 20,))
    g = jax.random.normal(jax.random.fold_in(key, 1), (1 << 20,))
    f = jax.jit(lambda a, b, c: ref.ssca_update_ref(a, b, c, 0.5, 0.3, 0.2, 1e-5))
    print(f"kern.ssca_update.1M,{_time(f, w, buf, g):.0f},ref_cpu", flush=True)

    q = jax.random.normal(jax.random.fold_in(key, 5), (1, 8, 512, 64))
    k = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, 512, 64))
    v = jax.random.normal(jax.random.fold_in(key, 3), (1, 2, 512, 64))
    f = jax.jit(lambda a, b, c: ref.flash_attention_ref(a, b, c))
    print(f"kern.flash_attn.512,{_time(f, q, k, v):.0f},ref_cpu", flush=True)
