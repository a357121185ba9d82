"""Time FisherVector's two forms on the chip (run on a real TPU).

The twin of tools/conv_form_probe.py, for ``ops/fisher.fv_form``'s table
(ops/fisher.py's docstring; ROOFLINE.md, "At the published sizes"): for each
(vocab, d) at 64 images of a 375x500 image's 73,866 descriptors, the kernel
form against the XLA form (time a chunk with the chunk resident, the
device's own time by operation from a profiler trace), the two forms'
distance, and on a slice the kernel's statistics against the jnp forms that
round the moment products' operands to bfloat16 and not at all.

Usage:  python tools/fv_form_probe.py [--shapes 16x64,64x80,256x80]
            [--images 64] [--cols 73866] [--blocks 1024,2048,4096]
            [--out chiprun_out/fv_form_probe.json]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np
from conv_form_probe import _device_ops, _rel, _time

from keystone_tpu.ops import fisher, fv_pallas
from keystone_tpu.solvers.gmm import GaussianMixtureModel
from keystone_tpu.utils.platform import init_device

SLICE_IMAGES = 4


def _mixture(rng, d, k):
    """A mixture as EM leaves one on projected SIFT: centres many sigma
    from the origin (projection does not centre), a few sigma apart."""
    means = (3.0 + rng.normal(size=(d, k))).astype(np.float32)
    variances = rng.uniform(0.5, 2.0, (d, k)).astype(np.float32)
    weights = rng.dirichlet(np.full(k, 5.0)).astype(np.float32)
    return GaussianMixtureModel(means, variances, weights)


def _descriptors(key, gmm, n, cols):
    """[n, d, cols] on the device: each descriptor near a drawn centre."""
    kc, kn = jax.random.split(key)
    d = gmm.dim
    comp = jax.random.randint(kc, (n, cols), 0, gmm.k)
    noise = jax.random.normal(kn, (n, d, cols), jnp.float32)
    mu = jnp.moveaxis(jnp.asarray(gmm.means).T[comp], 2, 1)  # [n, d, cols]
    sd = jnp.moveaxis(jnp.sqrt(jnp.asarray(gmm.variances)).T[comp], 2, 1)
    return mu + 1.5 * sd * noise


def probe_shape(k, d, n, cols, blocks, reps, rng):
    gmm = _mixture(rng, d, k)
    node = fisher.FisherVector(gmm)
    x = jax.jit(_descriptors, static_argnums=(2, 3))(
        jax.random.PRNGKey(int(rng.integers(1 << 30))), gmm, n, cols
    )
    rec = {
        "vocab": k, "d": d, "images": n, "cols": cols,
        "stream_ratio": 7 * k / d,
        "rule": fisher.fv_form("tpu", (), False),
    }
    forms = {"xla": jax.jit(node._xla_form)}
    for block in blocks:
        forms[f"kernel_{block}"] = jax.jit(
            lambda b, block=block: fisher._fv_from_stats(
                *fv_pallas.fv_stats_pallas(
                    b, None, gmm.means, gmm.variances, gmm.weights, block=block
                ),
                gmm.means, gmm.variances, gmm.weights,
                jnp.full((b.shape[0],), b.shape[2], jnp.float32),
            )
        )
    outs = {}
    for name, fn in forms.items():
        try:
            outs[name] = np.asarray(fn(x))
            rec[f"{name}_chunk_ms"] = _time(fn, x, reps) * 1e3
            rec[f"{name}_device"] = _device_ops(fn, x, 2)
        except Exception as e:  # noqa: BLE001 — a form that does not fit is a finding
            rec[f"{name}_error"] = f"{type(e).__name__}: {e}"[:400]
    for name, out in outs.items():
        if name != "xla" and "xla" in outs:
            rec[f"{name}_vs_xla_rms"] = _rel(out, outs["xla"])
        rec[f"{name}_finite"] = bool(np.isfinite(out).all())
    xs = x[:SLICE_IMAGES]
    got = fv_pallas.fv_stats_pallas(xs, None, gmm.means, gmm.variances, gmm.weights)
    for label, dtype in (("bf16", jnp.bfloat16), ("f32", jnp.float32)):
        want = fv_pallas.fv_stats_jnp(
            xs, None, gmm.means, gmm.variances, gmm.weights, moment_dtype=dtype
        )
        rec[f"stats_vs_{label}_operands_rms"] = [
            _rel(np.asarray(g), np.asarray(w)) for g, w in zip(got, want)
        ]
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="16x64,16x80,64x64,64x80,256x64,256x80")
    ap.add_argument("--images", type=int, default=64)
    ap.add_argument("--cols", type=int, default=73866)
    ap.add_argument("--blocks", default=str(fv_pallas.BLOCK))
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    device = init_device()
    if device["platform"] != "tpu":
        raise SystemExit(f"fv_form_probe: the device is {device}, not a TPU")
    blocks = [int(b) for b in args.blocks.split(",")]
    rng = np.random.default_rng(29)
    record = {"device": device, "shapes": []}
    for shape in args.shapes.split(","):
        k, d = (int(v) for v in shape.split("x"))
        rec = probe_shape(k, d, args.images, args.cols, blocks, args.reps, rng)
        record["shapes"].append(rec)
        print(json.dumps(rec), flush=True)
        if args.out:  # after every shape: a call cut short keeps what it had
            os.makedirs(os.path.dirname(args.out), exist_ok=True)
            with open(args.out, "w") as fh:
                json.dump(record, fh, indent=1)


if __name__ == "__main__":
    main()
