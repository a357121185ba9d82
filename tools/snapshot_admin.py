#!/usr/bin/env python
"""Snapshot-cache administration (the operational counterpart of
trace_view.py, for core.snapshot roots).

A snapshot root (``KEYSTONE_SNAPSHOT_DIR`` / the workloads'
``--snapshotDir``) accumulates one directory per (tar, decode config,
chunking, featurizer) key, plus ``.tmp-*`` debris from crashed writes.
This tool makes that state inspectable and reclaimable:

    python tools/snapshot_admin.py ROOT list
    python tools/snapshot_admin.py ROOT inspect KEY_PREFIX
    python tools/snapshot_admin.py ROOT evict --key KEY_PREFIX
    python tools/snapshot_admin.py ROOT evict --temps        # crash debris
    python tools/snapshot_admin.py ROOT evict --invalid      # no/bad manifest
    python tools/snapshot_admin.py ROOT evict --stale --tar PATH [--batch N]

* ``list`` — every snapshot with key, mode, images, chunks, on-disk bytes,
  and committed/valid state (uncommitted temp dirs included).
* ``inspect`` — FULL shard validation of one snapshot: every shard's size
  and sha256 re-checked against the manifest (the same check the reader
  runs per chunk); violations listed.
* ``evict`` — remove by key prefix, remove uncommitted temp directories,
  remove directories with missing/invalid manifests, or remove snapshots
  STALE for a given tar (committed for the same tar file names but under
  a key that no longer matches the tar's current identity/config).

The first stdout line is a machine-readable JSON record (same
truncation-proof convention as chaos_run.py); a short human
summary follows.  Exit status: 0 ok, 1 bad arguments/validation failure.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from keystone_tpu.core import snapshot as ksnap  # noqa: E402


def _fmt_bytes(n: int) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if n < 1024 or unit == "GiB":
            return f"{n:.1f} {unit}" if unit != "B" else f"{n} B"
        n /= 1024
    return f"{n} B"


def _stale_dirs(root: str, tar_path: str, batch_size: int | None) -> list:
    """Committed DECODED snapshot dirs for ``tar_path``'s file names whose
    key no longer matches the tar's CURRENT identity/decode config.

    Each candidate's key is recomputed from its OWN manifest-recorded
    chunking (batch size + extra key material, written by the ingest tee),
    so a snapshot is classified stale only when its exact key can be
    recomputed and no longer matches — never because its batch size wasn't
    in a guessed probe list.  A manifest without recorded chunking is left
    alone unless ``batch_size`` supplies the missing value (refuse to
    guess on a destructive operation).  Featurized snapshots are
    deliberately excluded: their keys fold in a featurizer digest this
    tool cannot recompute, so every featurized snapshot would read as
    stale — evict those explicitly by key."""
    want_names = sorted(r["name"] for r in ksnap.tar_identity(tar_path))
    live_keys: dict = {}  # (batch, extra) -> recomputed key
    out = []
    for snap in ksnap.list_snapshots(root):
        if not snap.get("committed") or snap.get("mode") != "decoded":
            continue
        if snap.get("tar_names") != want_names:
            continue
        batch = snap.get("batch_size") or batch_size
        if not batch:
            continue  # no recorded chunking and no --batch: cannot prove stale
        ck = (int(batch), snap.get("extra"))
        if ck not in live_keys:
            live_keys[ck] = ksnap.snapshot_key(
                tar_path, batch_size=ck[0], mode="decoded", extra=ck[1]
            )
        if snap["key"] != live_keys[ck]:
            out.append(snap)
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser("snapshot_admin")
    p.add_argument("root", help="snapshot root (KEYSTONE_SNAPSHOT_DIR)")
    sub = p.add_subparsers(dest="cmd", required=True)
    sub.add_parser("list", help="inventory every snapshot under the root")
    ins = sub.add_parser(
        "inspect", help="full shard validation (size + sha256) of one key"
    )
    ins.add_argument("key_prefix", help="snapshot key prefix (>= 4 chars)")
    ev = sub.add_parser("evict", help="remove snapshot directories")
    ev.add_argument("--key", default=None, help="evict by key prefix")
    ev.add_argument(
        "--temps", action="store_true",
        help="evict uncommitted .tmp-* directories (crash debris)",
    )
    ev.add_argument(
        "--invalid", action="store_true",
        help="evict directories with missing/invalid manifests",
    )
    ev.add_argument(
        "--stale", action="store_true",
        help="evict snapshots whose key no longer matches --tar's current "
        "identity/decode config",
    )
    ev.add_argument("--tar", default=None, help="tar path for --stale")
    ev.add_argument(
        "--batch", type=int, default=None,
        help="stream batch size for --stale key matching of snapshots "
        "whose manifest predates recorded chunking (normally unneeded: "
        "the recorded batch size is used)",
    )
    a = p.parse_args(argv)

    if a.cmd == "list":
        snaps = ksnap.list_snapshots(a.root)
        record = {
            "metric": "snapshot_admin",
            "op": "list",
            "root": a.root,
            "snapshots": snaps,
            "total_bytes": sum(s.get("bytes", 0) for s in snaps),
        }
        print(json.dumps(record), flush=True)
        if not snaps:
            print(f"# {a.root}: no snapshots")
        for s in snaps:
            if s.get("committed"):
                print(
                    f"# {s['dir']}: mode={s['mode']} images={s['images']} "
                    f"chunks={s['chunks']} {_fmt_bytes(s['bytes'])} "
                    f"key={s['key'][:16]}..."
                )
            else:
                print(
                    f"# {s['dir']}: NOT COMMITTED ({s['reason']}, "
                    f"{_fmt_bytes(s['bytes'])})"
                )
        return 0

    if a.cmd == "inspect":
        if len(a.key_prefix) < 4:
            p.error("inspect wants a key prefix of >= 4 characters")
        problems = ksnap.validate(a.root, a.key_prefix)
        record = {
            "metric": "snapshot_admin",
            "op": "inspect",
            "root": a.root,
            "key_prefix": a.key_prefix,
            "ok": not problems,
            "problems": problems,
        }
        print(json.dumps(record), flush=True)
        if problems:
            for pr in problems:
                print(f"# BAD {pr}")
        else:
            print(f"# {a.key_prefix}: every shard validates")
        return 1 if problems else 0

    # evict
    if not (a.key or a.temps or a.invalid or a.stale):
        p.error("evict wants at least one of --key/--temps/--invalid/--stale")
    if a.stale and not a.tar:
        p.error("--stale needs --tar")
    if a.key and len(a.key) < 4:
        p.error("--key wants a key prefix of >= 4 characters")
    removed = []
    if a.key or a.temps:
        removed += ksnap.evict(a.root, key_prefix=a.key, temps=a.temps)
    if a.invalid:
        # Exact directory names: an invalid dir has no trustworthy key to
        # prefix-match on (and a garbage-derived prefix could sweep up
        # valid snapshots).
        bad = [
            s["dir"]
            for s in ksnap.list_snapshots(a.root)
            if not s.get("committed") and not s["dir"].startswith(".tmp-")
        ]
        if bad:
            removed += ksnap.evict(a.root, names=bad)
    if a.stale:
        for s in _stale_dirs(a.root, a.tar, a.batch):
            removed += ksnap.evict(a.root, key_prefix=s["key"])
    record = {
        "metric": "snapshot_admin",
        "op": "evict",
        "root": a.root,
        "removed": removed,
    }
    print(json.dumps(record), flush=True)
    print(f"# evicted {len(removed)} director{'y' if len(removed) == 1 else 'ies'}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
