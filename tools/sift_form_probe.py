"""Time SIFTExtractor's two forms on the chip (run on a real TPU).

The twin of tools/conv_form_probe.py and tools/fv_form_probe.py, for
``ops/sift.sift_form`` (ROOFLINE.md, "At the published sizes"): for each
shape the chunk program ``fv_common._describe_chunk`` in the kernel form
(``ops/sift_pallas.assemble_scale``) against the XLA form, a chunk resident
on the device: wall a chunk, the device's own time by operation from a
profiler trace (every operation from ``--floor`` ms up, so the XLA form's
assembly can be read off by name), and how far the two forms' bytes lie
apart.  The XLA form is asked for by replacing ``sift.sift_form`` here, in
the probe; no option of the program selects it.

Usage:  python tools/sift_form_probe.py [--shapes 64x375x500,64x333x500,32x256x256s1]
            [--reps 5] [--floor 0.3] [--out chiprun_out/sift_form_probe.json]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np
from conv_form_probe import _device_ops, _time

from keystone_tpu.ops import sift
from keystone_tpu.utils.platform import init_device
from keystone_tpu.workloads import fv_common


def _images(rng, n, h, w):
    """Seeded byte images with structure: a grating an image, noise, and one
    image of no contrast (its descriptors are zeros in both forms)."""
    yy, xx = np.mgrid[0:h, 0:w]
    angle = rng.uniform(0, np.pi, (n, 1, 1))
    wave = 60 * np.sin((xx * np.cos(angle) + yy * np.sin(angle)) / rng.uniform(2, 9, (n, 1, 1)))
    img = 128 + wave[..., None] + rng.normal(0, 20, (n, h, w, 3))
    img[n // 2] = 77
    return np.clip(img, 0, 255).astype(np.uint8)


def _ops_from(fn, x, reps, floor_ms):
    """``_device_ops`` with every operation from ``floor_ms`` up by name."""
    dev = _device_ops(fn, x, reps, top=400)
    return {
        "busy_ms": dev["busy_ms"],
        "ops_ms": [o for o in dev["ops_ms"] if o[1] >= floor_ms],
        "below_floor_ms": sum(o[1] for o in dev["ops_ms"] if o[1] < floor_ms),
    }


def probe_shape(n, h, w, scale_step, reps, floor_ms, rng):
    node = sift.SIFTExtractor(scale_step=scale_step, compute_dtype=jnp.bfloat16)
    flat = jax.device_put(_images(rng, n, h, w).reshape(n, -1))
    rec = {
        "images": n, "h": h, "w": w, "scale_step": scale_step,
        "descriptors": node.num_descriptors(h, w),
        "rule": sift.sift_form(
            "tpu", (), jnp.bfloat16, n, max(len(xs) for _b, _ys, xs in node._grids(h, w))
        ),
    }
    rule = sift.sift_form
    outs = {}
    for form in ("kernel", "xla"):
        sift.sift_form = (lambda *a, form=form: form)
        fv_common._describe_chunk.clear_cache()
        try:
            fn = lambda x: fv_common._describe_chunk(node, x, image_shape=(h, w, 3))  # noqa: E731
            outs[form] = np.asarray(fn(flat))
            rec[form] = {"wall_ms": _time(fn, flat, reps) * 1e3, **_ops_from(fn, flat, reps, floor_ms)}
        except Exception as e:  # noqa: BLE001 — a form Mosaic refuses is a finding
            rec[form] = {"error": f"{type(e).__name__}: {str(e)[:2000]}"}
        finally:
            sift.sift_form = rule
    fv_common._describe_chunk.clear_cache()
    if len(outs) == 2:
        k, x = outs["kernel"].astype(np.int32), outs["xla"].astype(np.int32)
        diff = np.abs(k - x)
        rec["kernel_vs_xla"] = {
            "identical_share": float((diff == 0).mean()),
            "within_one_share": float((diff <= 1).mean()),
            "max": int(diff.max()),
            "zero_contrast_max": [int(k[n // 2].max()), int(x[n // 2].max())],
            "mean_byte": [float(k.mean()), float(x.mean())],
        }
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="64x375x500,64x333x500,32x256x256s1")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--floor", type=float, default=0.3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    device = init_device()
    if device["platform"] != "tpu":
        raise SystemExit(f"sift_form_probe: the device is {device}, not a TPU")
    rng = np.random.default_rng(31)
    record = {"device": device, "shapes": []}
    for shape in args.shapes.split(","):
        dims, _, step = shape.partition("s")
        n, h, w = (int(v) for v in dims.split("x"))
        rec = probe_shape(n, h, w, int(step or 0), args.reps, args.floor, rng)
        record["shapes"].append(rec)
        print(json.dumps(rec), flush=True)
        if args.out:  # after every shape: a call cut short keeps what it had
            os.makedirs(os.path.dirname(args.out), exist_ok=True)
            with open(args.out, "w") as fh:
                json.dump(record, fh, indent=1)


if __name__ == "__main__":
    main()
