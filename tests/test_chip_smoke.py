"""chip_smoke.py's refusals and the platform helper it rests on.  Seconds
long each: the full rehearsal (``python chip_smoke.py --rehearsal``) is run
by hand, and the legs themselves only mean something on the chip."""

import json
import os
import shutil
import subprocess
import sys
import types

import pytest

import chip_smoke
from keystone_tpu.utils import platform

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def smoke_env(monkeypatch, tmp_path):
    """main() sets process-wide env and writes under the checkout: give
    both back."""
    for var in ("KEYSTONE_PLAN_LOG", "KEYSTONE_PALLAS_IDCT"):
        monkeypatch.setenv(var, os.environ.get(var, ""))
    monkeypatch.setattr(chip_smoke, "WORK", str(tmp_path / "work"))


def test_smoke_refuses_non_tpu_without_rehearsal(smoke_env, capsys):
    """The test platform is the CPU: exit 2, before any work, and no
    result on stdout."""
    assert chip_smoke.main([]) == 2
    out = capsys.readouterr()
    assert out.out == ""
    assert "not a TPU" in out.err
    assert not os.path.exists(chip_smoke.WORK)


def test_smoke_exits_nonzero_when_a_leg_raises(smoke_env, capsys, monkeypatch):
    def boom(ctx):
        raise RuntimeError("the chip said no")

    monkeypatch.setitem(chip_smoke.LEGS, "D", boom)
    assert chip_smoke.main(["--rehearsal", "--legs", "D"]) == 1
    lines = capsys.readouterr().out.splitlines()
    final = json.loads(lines[-1])
    assert final == {
        "rehearsal": True, "passed": False, "device": final["device"],
    }
    summary = json.loads(lines[-2])
    assert summary["failed"] == ["D"] and summary["claim"] is None
    assert list(summary)[-1] == "claim"
    assert "the chip said no" in json.loads(lines[-3])["error"]
    assert not os.path.exists(chip_smoke.WORK)  # cleaned up on the way out


def test_a_rehearsal_or_subset_never_says_ok(smoke_env, capsys, monkeypatch):
    monkeypatch.setitem(chip_smoke.LEGS, "D", lambda ctx: {})
    assert chip_smoke.main(["--rehearsal", "--legs", "D"]) == 0
    final = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert "ok" not in final and final["rehearsal"] and final["passed"]


def _fake_devices(platform_name, kind):
    return lambda: [types.SimpleNamespace(platform=platform_name,
                                          device_kind=kind)]


def test_compile_cache_is_placed_from_outside_when_set(monkeypatch):
    import jax

    updates = []
    monkeypatch.setattr(jax, "devices", _fake_devices("tpu", "TPU v5 lite"))
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: updates.append((k, v))
    )
    monkeypatch.setenv(platform.COMPILE_CACHE_ENV, "/some/dir")
    assert platform.compile_cache_dir() == "/some/dir"
    assert platform.init_device() == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1,
    }
    assert updates == []  # JAX reads its own variable; nothing is touched

    monkeypatch.delenv(platform.COMPILE_CACHE_ENV)
    platform.init_device()
    assert ("jax_compilation_cache_dir",
            os.path.join(_REPO, ".jax_cache")) in updates

    # the CPU (tests, rehearsals) caches nothing
    del updates[:]
    monkeypatch.setattr(jax, "devices", _fake_devices("cpu", "cpu"))
    platform.init_device()
    assert updates == []


def test_compile_cache_path_is_the_same_from_two_processes():
    env = {k: v for k, v in os.environ.items()
           if k != platform.COMPILE_CACHE_ENV}
    env["PYTHONPATH"] = _REPO
    script = (
        "from keystone_tpu.utils.platform import compile_cache_dir; "
        "print(compile_cache_dir())"
    )
    paths = [
        subprocess.run(
            [sys.executable, "-c", script], cwd=cwd, env=env,
            capture_output=True, text=True, timeout=60, check=True,
        ).stdout.strip()
        for cwd in (_REPO, os.path.join(_REPO, "tests"))
    ]
    assert paths[0] == paths[1] == os.path.join(_REPO, ".jax_cache")


def test_native_library_is_named_by_its_source(tmp_path):
    """An edited source gets another file name, so a binary that came
    along with a copied tree is never loaded for it."""
    if shutil.which("g++") is None:
        pytest.skip("no g++")
    src = str(tmp_path / "entropy.cpp")
    shutil.copy(
        os.path.join(_REPO, "keystone_tpu", "native", "entropy.cpp"), src
    )
    first = platform.build_native_library(src, "kstentropy")
    assert first is not None and os.path.dirname(first) == str(
        tmp_path / "build"
    )
    assert platform.build_native_library(src, "kstentropy") == first
    with open(src, "a") as f:
        f.write("\n// edited\n")
    second = platform.build_native_library(src, "kstentropy")
    assert second is not None and second != first
    assert os.path.exists(first) and os.path.exists(second)
