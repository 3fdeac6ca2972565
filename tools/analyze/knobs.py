"""Pass 2 — the config-knob registry.

Extracts every ``HOROVOD_*`` / ``HVD_*`` environment variable read across
Python (AST), C++ (cc/src getenv sites) and the tools/bench surface into a
generated registry — ``docs/config_registry.json`` — and checks:

- every knob read in code is documented (README.md or docs/*.md);
- every knob documented in prose is alive in code (no documented-but-dead
  names drifting in the docs);
- knobs read on BOTH sides of the ctypes bridge agree on their default
  (the python Config and the C++ getenv fallback must resolve the same
  value when the env var is unset);
- two python read sites of the same knob agree on their default.

The registry is the machine-readable config surface: docs/analysis.md
describes how the README table is kept in sync with it.
"""

from __future__ import annotations

import json
import os
from typing import Optional

from . import cpp, pysrc
from .common import (KNOB_MENTION_RE, Finding, make_finding,
                     normalize_default, parse_py, py_files, read_text)

REGISTRY_REL = os.path.join("docs", "config_registry.json")

#: python scan scope (tools/analyze is always excluded by py_files)
PY_SCOPE = ("horovod_tpu", "tools")
CPP_DIR = os.path.join("horovod_tpu", "cc", "src")
DOC_FILES = ("README.md",)
DOC_DIR = "docs"

#: C++ defaults that the literal-idiom extractor cannot read (reversed
#: boolean tests, enum translations). Each entry is the value the native
#: side EFFECTIVELY uses when the env var is unset; keep in sync with the
#: cited source. These participate in the cross-default check exactly like
#: extracted literals.
NATIVE_SEMANTIC_DEFAULTS = {
    # engine.cc wait_for_work: on unless the env var is literally "0"
    "HOROVOD_WAKE_ON_ENQUEUE": True,
    # engine.cc: tracing disabled when HOROVOD_TRACE_DIR is unset/empty
    "HOROVOD_TRACE_DIR": "",
    # net.h job_secret(): empty string disables authentication
    "HOROVOD_SECRET": "",
    # c_api.cc: malloc tuning applied unless the flag is set
    "HOROVOD_NO_MALLOC_TUNING": False,
    # engine.h wire_dtype_from_env(): -1 (no wire cast) == "none"
    "HOROVOD_COMPRESSION": "none",
}

#: knobs whose python and native defaults are INTENTIONALLY incomparable
#: (different representations of the same semantics, verified by the
#: cross-engine tests instead). Keep small; explain every entry.
CROSS_DEFAULT_EXEMPT: dict[str, str] = {}

#: launcher-set identity envelope, not tunables: every process is HANDED
#: these; a read site's fallback ("?" in a log line, 0 in a single-process
#: topology) is context display, not a config default — so the registry
#: records no default and the default-conflict checks skip them.
IDENTITY_KNOBS = {
    "HOROVOD_RANK", "HOROVOD_SIZE", "HOROVOD_LOCAL_RANK",
    "HOROVOD_LOCAL_SIZE", "HOROVOD_CROSS_RANK", "HOROVOD_CROSS_SIZE",
    "HOROVOD_TASK_INDEX", "HOROVOD_HOSTNAME",
}


def _doc_text(root: str) -> str:
    parts = [read_text(root, f) for f in DOC_FILES]
    doc_dir = os.path.join(root, DOC_DIR)
    for fn in sorted(os.listdir(doc_dir)):
        if fn.endswith(".md"):
            parts.append(read_text(root, os.path.join(DOC_DIR, fn)))
    return "\n".join(parts)


def extract(root: str) -> dict:
    """-> {"knobs": {...}, "doc_mentions": set, "py_conflicts": {...}}"""
    py_reads: dict[str, list[pysrc.PyEnvRead]] = {}
    py_writes: dict[str, list[tuple[str, int]]] = {}
    for rel in py_files(root, PY_SCOPE):
        try:
            module = parse_py(root, rel)
        except SyntaxError:
            continue
        reads, writes = pysrc.find_env_reads(module, rel)
        for r in reads:
            py_reads.setdefault(r.knob, []).append(r)
        for knob, line in writes:
            py_writes.setdefault(knob, []).append((rel, line))

    cc_reads: dict[str, list[cpp.CppEnvRead]] = {}
    cpp_dir = os.path.join(root, CPP_DIR)
    for fn in sorted(os.listdir(cpp_dir)):
        if not (fn.endswith(".h") or fn.endswith(".cc")):
            continue
        rel = os.path.join(CPP_DIR, fn)
        for r in cpp.find_getenv(read_text(root, rel), rel):
            cc_reads.setdefault(r.knob, []).append(r)

    doc_mentions = set(KNOB_MENTION_RE.findall(_doc_text(root)))

    knobs: dict[str, dict] = {}
    for name in sorted(set(py_reads) | set(cc_reads)):
        entry: dict = {}
        if name in py_reads:
            reads = py_reads[name]
            defaults = sorted(
                {json.dumps(normalize_default(r.default), sort_keys=True)
                 for r in reads if r.default_known and not r.indirect})
            side = {"files": sorted({r.path for r in reads})}
            if name in IDENTITY_KNOBS:
                side["identity"] = True
            elif len(defaults) == 1:
                side["default"] = json.loads(defaults[0])
            elif defaults:
                side["defaults"] = [json.loads(d) for d in defaults]
            entry["python"] = side
        if name in cc_reads:
            reads_c = cc_reads[name]
            side = {"files": sorted({r.path for r in reads_c})}
            if name in NATIVE_SEMANTIC_DEFAULTS:
                side["default"] = NATIVE_SEMANTIC_DEFAULTS[name]
                side["annotated"] = True
            else:
                defaults = sorted(
                    {json.dumps(normalize_default(r.default), sort_keys=True)
                     for r in reads_c if r.default_known})
                if len(defaults) == 1:
                    side["default"] = json.loads(defaults[0])
                elif defaults:
                    side["defaults"] = [json.loads(d) for d in defaults]
            entry["native"] = side
        entry["documented"] = name in doc_mentions
        knobs[name] = entry

    return {
        "knobs": knobs,
        "doc_mentions": doc_mentions,
        "py_writes": py_writes,
    }


def registry_dict(root: str, extracted: Optional[dict] = None) -> dict:
    if extracted is None:
        extracted = extract(root)
    return {
        "$comment": (
            "GENERATED by `python -m tools.analyze --emit-spec` — every "
            "HOROVOD_*/HVD_* environment variable read by the python "
            "engine, the native engine, and the tools, with the default "
            "each side resolves when the variable is unset. CI "
            "regenerates this file and fails on any diff "
            "(docs/analysis.md). Do not edit by hand."),
        "version": 1,
        "knobs": extracted["knobs"],
    }


def render(registry: dict) -> str:
    return json.dumps(registry, indent=2, ensure_ascii=False) + "\n"


def check(root: str, extracted: Optional[dict] = None) -> list[Finding]:
    if extracted is None:
        extracted = extract(root)
    findings: list[Finding] = []
    knobs = extracted["knobs"]
    doc_mentions = extracted["doc_mentions"]

    if not knobs:
        return [make_finding("knobs", "extraction-failed", "all",
                             "no env knobs extracted at all — the scan "
                             "scope or the extractor is broken")]

    for name, entry in knobs.items():
        if not entry["documented"]:
            findings.append(make_finding(
                "knobs", "undocumented", name,
                f"{name} is read in code "
                f"({', '.join((entry.get('python') or entry.get('native'))['files'][:2])}) "
                "but never mentioned in README.md or docs/*.md — add it to "
                "the README config table", ))
        py_side = entry.get("python")
        if py_side and "defaults" in py_side:
            findings.append(make_finding(
                "knobs", "py-default-conflict", name,
                f"{name} is read at multiple python sites with different "
                f"defaults {py_side['defaults']!r} "
                f"({', '.join(py_side['files'])}) — one site must become "
                "authoritative"))
        native_side = entry.get("native")
        if native_side and "defaults" in native_side:
            findings.append(make_finding(
                "knobs", "native-default-conflict", name,
                f"{name} has conflicting native defaults "
                f"{native_side['defaults']!r}"))
        if (py_side and native_side and name not in CROSS_DEFAULT_EXEMPT
                and "default" in py_side and "default" in native_side):
            a = normalize_default(py_side["default"])
            b = normalize_default(native_side["default"])
            # bools compare against 0/1 spellings across the bridge
            norm = lambda v: int(v) if isinstance(v, bool) else v
            if norm(a) != norm(b):
                findings.append(make_finding(
                    "knobs", "cross-default-mismatch", name,
                    f"{name}: python default {a!r} "
                    f"({', '.join(py_side['files'])}) vs native default "
                    f"{b!r} ({', '.join(native_side['files'])}) — the two "
                    "engines resolve different values when the env var is "
                    "unset"))

    referenced = set(knobs) | set(extracted["py_writes"])
    for name in sorted(doc_mentions):
        if name not in referenced:
            findings.append(make_finding(
                "knobs", "documented-dead", name,
                f"{name} appears in README/docs but nothing in "
                "horovod_tpu/ or tools/ reads or sets it — "
                "delete the stale mention or alias the knob"))
    return findings


def check_registry_file(root: str,
                        extracted: Optional[dict] = None) -> list[Finding]:
    rendered = render(registry_dict(root, extracted))
    path = os.path.join(root, REGISTRY_REL)
    if not os.path.exists(path):
        return [make_finding(
            "spec", "missing", "config_registry",
            f"{REGISTRY_REL} is missing — run `python -m tools.analyze "
            "--emit-spec` and commit the result", REGISTRY_REL)]
    with open(path, encoding="utf-8") as f:
        if f.read() != rendered:
            return [make_finding(
                "spec", "stale", "config_registry",
                f"{REGISTRY_REL} does not match the knobs extracted from "
                "the current sources — run `python -m tools.analyze "
                "--emit-spec` and commit the regenerated file",
                REGISTRY_REL)]
    return []


def emit(root: str) -> str:
    path = os.path.join(root, REGISTRY_REL)
    with open(path, "w", encoding="utf-8") as f:
        f.write(render(registry_dict(root)))
    return path
