"""Reproduce the ROOFLINE.md featurize-variant table (run on a real TPU).

Times the shipped fused compact-activation featurizer against the op-by-op
XLA chain and the f32-exactness variant at the bench shape, with XLA
cost-analysis FLOPs/bytes — the measurements behind ops/conv_fused.py's
design.  Usage:  python tools/roofline_probe.py
"""

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from bench import compiled_cost, timed_chain_auto
from keystone_tpu.core.optimize import DEVICE_RATES
from keystone_tpu.utils.platform import init_device
from keystone_tpu.workloads.cifar_random_patch import (
    RandomCifarConfig,
    build_conv_pipeline,
    learn_filters,
)


def main():
    device = init_device()
    if device["platform"] != "tpu":
        raise SystemExit(f"roofline_probe: the device is {device}, not a TPU")
    rates = DEVICE_RATES[device["kind"]]  # a TPU not in the table is an error
    peak, bw = rates["peak_flops"], rates["hbm_gbps"] * 1e9
    print(
        f"# device: {json.dumps(device)}  peak={peak / 1e12:.0f} TFLOP/s  "
        f"hbm={bw / 1e9:.0f} GB/s"
    )

    # 100 filters: FusedConvFeaturizer takes its XLA form (conv_form's
    # rule); tools/conv_form_probe.py times the kernel form.
    conf = RandomCifarConfig(
        num_filters=100, patch_size=6, patch_steps=1, pool_size=14,
        pool_stride=13, alpha=0.25, whitener_size=20000, featurize_chunk=1024,
    )
    rng = np.random.default_rng(0)
    train = rng.uniform(0, 255, (512, 32, 32, 3)).astype(np.float32)
    filters, whitener = learn_filters(conf, train)
    batch = jnp.asarray(rng.uniform(0, 255, (1024, 32, 32, 3)).astype(np.float32))

    def conv_pipe(fused, dtype=jnp.bfloat16):
        pipe = build_conv_pipeline(conf, filters, whitener, fused=fused)
        if fused:
            pipe.nodes[0].activation_dtype = dtype
        return pipe

    ref = np.asarray(jax.jit(conv_pipe(True).__call__)(batch))
    cases = [
        ("unfused_xla_f32", conv_pipe(False)),
        ("fused_bf16_SHIPPED", conv_pipe(True)),
        ("fused_f32_exact", conv_pipe(True, jnp.float32)),
    ]
    for name, pipe in cases:
        j = jax.jit(pipe.__call__)
        got = np.asarray(j(batch))
        err = float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))
        per = timed_chain_auto(pipe.__call__, batch, chain_len=64)
        fl, by = compiled_cost(j, batch)
        rec = {
            "case": name,
            "images_per_sec": round(1024 / per, 1),
            "tflops": round(fl / per / 1e12, 2) if fl else None,
            "bytes_per_img": round(by / 1024) if by else None,
            "rel_err_vs_shipped": float(f"{err:.2e}"),
        }
        if fl and by:
            intensity = fl / by
            rec["fraction_of_ceiling"] = round(
                (fl / per) / min(intensity * bw, peak), 3
            )
        print(json.dumps(rec))


if __name__ == "__main__":
    main()
