"""Kernel micro-benchmarks, compiled for the TPU. Host wall times per call
(after a compile-and-warm-up call, each ending in ``block_until_ready``);
on any other device the module refuses to run rather than time the
Pallas interpreter."""
import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import row, timed
from repro.core.quantize import quantize_int4, quantize_int8
from repro.kernels.flash_decode import flash_decode
from repro.kernels.qmatmul import qmatmul


def run():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(f"kernel_bench needs a TPU; JAX found "
                           f"{dev.platform} ({dev.device_kind})")
    key = jax.random.PRNGKey(0)
    rows = []
    B, K, N = 4, 512, 512
    x = jax.random.normal(key, (B, K))
    w = jax.random.normal(key, (K, N)) / np.sqrt(K)
    for prec in ("fp", "int8", "int4"):
        if prec == "fp":
            args = (x, w, None)
        elif prec == "int8":
            args = (x, *quantize_int8(w, 0))
        else:
            args = (x, *quantize_int4(w, 0))
        _, us = timed(lambda a=args, p=prec: jax.block_until_ready(
            qmatmul(a[0], a[1], a[2], precision=p, interpret=False)),
            repeats=2)
        bytes_w = args[1].nbytes
        rows.append(row(f"kernel.qmatmul.{prec}", us,
                        f"weight bytes {bytes_w} "
                        f"({bytes_w / (K * N * 2):.2f}x of bf16) "
                        f"on {dev.device_kind}"))

    q = jax.random.normal(key, (1, 2, 4, 128))
    k = jax.random.normal(key, (1, 1024, 2, 128))
    v = jax.random.normal(key, (1, 1024, 2, 128))
    pos = jnp.broadcast_to(jnp.arange(1024)[None], (1, 1024))
    lens = jnp.array([900])
    _, us = timed(lambda: jax.block_until_ready(
        flash_decode(q, k, v, pos, lens, bs=256, interpret=False)),
        repeats=2)
    rows.append(row("kernel.flash_decode.s1024", us,
                    f"on {dev.device_kind}"))
    return rows
