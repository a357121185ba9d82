"""Time FusedConvFeaturizer's two forms on the chip (run on a real TPU).

Reproduces ROOFLINE.md's "1,250 filters" section: for each shape, the
kernel form against the XLA form (time a chunk with the chunk resident,
the device's own time by operation from a profiler trace, the two forms'
distance) and both against the benchmark's plain reference on a slice;
and the rate at which a chunk of pixels crosses host -> device, which
caps what a faster featurizer can show end to end.

Usage:  python tools/conv_form_probe.py [--shapes 2048x1250,1024x100]
            [--tile 256] [--rows 3] [--out chiprun_out/conv_form_probe.json]
"""

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import xplane
from benchmark.lib.manifest import load_module
from keystone_tpu.ops import conv_fused
from keystone_tpu.utils.platform import init_device

REFERENCE_IMAGES = 128


def _images(rng, n):
    """Seeded images with structure: a palette a class, a wave, noise."""
    base = rng.uniform(40, 215, (n, 1, 1, 3))
    yy, xx = np.mgrid[0:32, 0:32]
    wave = 30 * np.sin(xx / rng.uniform(2, 6, (n, 1, 1)))[..., None]
    img = base + wave + rng.normal(0, 25, (n, 32, 32, 3))
    return np.clip(img, 0, 255).astype(np.float32)


def _time(fn, x, reps):
    fn(x).block_until_ready()
    t0 = time.perf_counter()
    out = None
    for _ in range(reps):
        out = fn(x)
    out.block_until_ready()
    return (time.perf_counter() - t0) / reps


def _device_ops(fn, x, reps, top=8):
    """Seconds a call by operation (the ``top`` longest), from the device's
    own trace."""
    with tempfile.TemporaryDirectory() as logdir:
        with jax.profiler.trace(logdir):
            for _ in range(reps):
                out = fn(x)
            out.block_until_ready()
        plain = xplane.plain_from_xplane(xplane.find_xplane(logdir))
    dev = xplane.reduce_trace(plain, window=(0, float("inf")))["devices"][0]
    return {
        "busy_ms": dev["busy_ns"] / reps / 1e6,
        "ops_ms": [[name, s * 1e3 / reps] for name, s in xplane.top(dev["ops"], top)],
    }


def _rel(a, b):
    return float(np.sqrt(np.mean((a - b) ** 2)) / np.sqrt(np.mean(b**2)))


def probe_shape(n, f, reps, rng):
    reference = load_module("reference", "cifar_rp")
    filters = rng.normal(size=(f, 6, 6, 3)).astype(np.float32)
    filters /= np.linalg.norm(filters.reshape(f, -1), axis=1)[:, None, None, None]
    means = (0.1 * rng.normal(size=(108,))).astype(np.float32)
    node = conv_fused.FusedConvFeaturizer(
        filters, whitener_means=means, pool_stride=13, pool_size=14, alpha=0.25
    )
    x = jnp.asarray(_images(rng, n))
    forms = {"kernel": jax.jit(node._kernel_form), "xla": jax.jit(node._xla_form)}
    rec = {"images": n, "filters": f, "rule": conv_fused.conv_form("tpu", 729, 108, f, True)}
    outs = {}
    for name, fn in forms.items():
        t0 = time.perf_counter()
        outs[name] = np.asarray(fn(x))
        rec[f"{name}_first_call_s"] = time.perf_counter() - t0
        rec[f"{name}_chunk_ms"] = _time(fn, x, reps) * 1e3
        rec[f"{name}_device"] = _device_ops(fn, x, 4)
    want = np.asarray(
        reference._featurize_chunk(
            x[:REFERENCE_IMAGES], jnp.asarray(filters.reshape(f, -1)),
            jnp.asarray(means), 0.25, ps=6, pool=14, stride=13, precision="highest",
        )
    )
    rec["kernel_vs_xla_rms"] = _rel(outs["kernel"], outs["xla"])
    rec["kernel_vs_reference_rms"] = _rel(outs["kernel"][:REFERENCE_IMAGES], want)
    rec["xla_vs_reference_rms"] = _rel(outs["xla"][:REFERENCE_IMAGES], want)
    rec["kernel_vs_reference_max"] = float(
        np.abs(outs["kernel"][:REFERENCE_IMAGES] - want).max() / np.abs(want).max()
    )
    rec["xla_vs_reference_max"] = float(
        np.abs(outs["xla"][:REFERENCE_IMAGES] - want).max() / np.abs(want).max()
    )
    return rec


def probe_h2d(rng, copies=25):
    """A chunk of pixels as the fit copies it: one ``jnp.asarray`` of
    2,048 images from host memory, waited for; and the same bytes as a
    matrix ``[2048, 3072]``, whose device layout is the host's order (an
    image batch's puts the batch innermost)."""
    chunk = _images(rng, 2048)
    out = {"chunk_mb": chunk.nbytes / 1e6}
    for name, arr in (("images", chunk), ("matrix", chunk.reshape(2048, -1))):
        jnp.asarray(arr).block_until_ready()
        times = []
        for _ in range(copies):
            t0 = time.perf_counter()
            jnp.asarray(arr).block_until_ready()
            times.append(time.perf_counter() - t0)
        med = float(np.median(times))
        out[name] = {
            "copy_ms_median": med * 1e3,
            "copy_ms_min": min(times) * 1e3,
            "copy_ms_max": max(times) * 1e3,
            "gb_per_s_median": arr.nbytes / med / 1e9,
        }
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="2048x1250,1024x100")
    ap.add_argument("--tile", type=int, default=conv_fused._FILTER_TILE)
    ap.add_argument("--rows", type=int, default=conv_fused._ROWS_PER_PRODUCT)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    device = init_device()
    if device["platform"] != "tpu":
        raise SystemExit(f"conv_form_probe: the device is {device}, not a TPU")
    conv_fused._FILTER_TILE = args.tile
    conv_fused._ROWS_PER_PRODUCT = args.rows
    rng = np.random.default_rng(27)
    record = {"device": device, "tile": args.tile, "rows": args.rows}
    record["h2d"] = probe_h2d(rng)
    print(json.dumps({"h2d": record["h2d"]}), flush=True)
    record["shapes"] = []
    for shape in args.shapes.split(","):
        n, f = (int(v) for v in shape.split("x"))
        rec = probe_shape(n, f, args.reps, rng)
        record["shapes"].append(rec)
        print(json.dumps(rec), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(record, fh, indent=1)


if __name__ == "__main__":
    main()
