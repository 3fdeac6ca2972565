"""The tree's own shape: what ``ci.sh`` runs exists, and what PR 41 deleted
(the root-level bench script, the scaling harness and its records, the
reviewer cross-walks, the capped-trace roofline reader) is named nowhere
but in the history files."""

from __future__ import annotations

import functools
import importlib.util
import os
import re
import subprocess

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# History, or not the builder's to edit; and this file, which must name
# what it looks for.
EXEMPT = {"CHANGES.md", "ROADMAP.md", "ISSUE.md", "REVIEW.md",
          "PERF_LEDGER.jsonl", os.path.join("tests", "test_tree.py")}
GONE = {
    "bench.py": r"(?<!\w)bench\.py",
    "import bench": r"\bimport bench\b",
    "scaling_benchmark": r"scaling_benchmark",
    "SCALING_r0": r"SCALING_r0",
    "RESPONSE_r0": r"RESPONSE_r0",
    "profile_device_ops": r"profile_device_ops",
}
# What a checkout without ``.git`` (the driver's) is walked without.
UNTRACKED_DIRS = {".git", "__pycache__", ".pytest_cache", ".hypothesis",
                  ".scratch", ".jax_cache", ".jax_cache_big", ".bench_out",
                  "chiprun_out", "horovod_tpu.egg-info", "build", "dist-ci"}


def _ci_targets() -> list[str]:
    """``tools/x.py`` for every ``python <path>`` and ``-m mod`` for every
    ``python -m <module>`` of ci.sh (heredocs, ``python -``, are inline)."""
    with open(os.path.join(REPO, "ci.sh"), encoding="utf-8") as f:
        text = f.read()
    found = re.findall(r"\bpython\s+(-m\s+[\w.]+|[\w./]+\.py)\b", text)
    return sorted({re.sub(r"\s+", " ", t) for t in found})


@functools.lru_cache(maxsize=None)
def _tracked_text() -> dict:
    try:
        names = subprocess.run(
            ["git", "ls-files", "-z"], cwd=REPO, check=True,
            capture_output=True, text=True).stdout.split("\0")
    except (OSError, subprocess.CalledProcessError):
        names = []
        for base, dirs, files in os.walk(REPO):
            dirs[:] = [d for d in dirs if d not in UNTRACKED_DIRS]
            names += [os.path.relpath(os.path.join(base, f), REPO)
                      for f in files if not f.endswith((".pyc", ".so"))]
    out = {}
    for name in names:
        path = os.path.join(REPO, name)
        if name and name not in EXEMPT and os.path.isfile(path):
            with open(path, encoding="utf-8", errors="replace") as f:
                out[name] = f.read()
    return out


def test_ci_sh_still_has_legs_to_check():
    targets = _ci_targets()
    assert "-m tools.analyze" in targets and "tools/eager_smoke.py" in targets


@pytest.mark.parametrize("target", _ci_targets())
def test_every_leg_of_ci_sh_exists(target):
    if target.startswith("-m "):
        assert importlib.util.find_spec(target[3:]) is not None, target
    else:
        assert os.path.isfile(os.path.join(REPO, target)), target


@pytest.mark.parametrize("gone", sorted(GONE))
def test_no_tracked_file_names_what_was_deleted(gone):
    pattern = re.compile(GONE[gone])
    hits = [f"{name}:{text.count(chr(10), 0, m.start()) + 1}"
            for name, text in _tracked_text().items()
            for m in [pattern.search(text)] if m]
    assert not hits, f"{gone!r} is still named in {hits}"


def test_deleted_files_stay_deleted():
    back = [p for p in (
        "bench.py", "examples/scaling_benchmark.py", "tests/test_scaling.py",
        "tests/test_bench.py", "docs/scaling.md", "SCALING_r04.json",
        "SCALING_r05.json", "RESPONSE_r03.md", "RESPONSE_r04.md")
        if os.path.exists(os.path.join(REPO, p))]
    assert not back, back


def test_roofline_module_is_the_peak_table_alone():
    """One operator's profile entry: ``hvd.metrics.profile_step``. The old
    reader of the viewer's capped export must not grow back."""
    import horovod_tpu.utils.roofline as roofline

    public = {n for n in vars(roofline) if not n.startswith("_")}
    assert public - {"annotations"} == {"DEVICE_PEAKS", "device_peaks"}
