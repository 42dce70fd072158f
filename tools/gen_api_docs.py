"""Generate docs/api.md from the public-module docstrings.

Usage::

    PYTHONPATH=src python tools/gen_api_docs.py [--check]

``--check`` exits non-zero when docs/api.md is stale (CI hook).  The
reference is intentionally committed: the docs must be readable on the
repo host without running anything.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib
import inspect
import re
import sys
from pathlib import Path

MODULES = [
    ("repro.api", "Front-end"),
    ("repro.core.problem", "General-form problems"),
    ("repro.core.backends", "Backends, options, stats"),
    ("repro.core.tableau", "Tableau storage layer"),
    ("repro.core.engine", "Simplex iteration engine"),
    ("repro.core.pdhg", "First-order (restarted PDHG) backend"),
    ("repro.core.revised", "Shared-structure revised simplex"),
    ("repro.core.dispatch", "Dispatch round-scheduler"),
    ("repro.core.session", "Persistent solve sessions"),
    ("repro.core.lp", "Canonical containers and tableau"),
    ("repro.core.bucketing", "Shape bucketing"),
    ("repro.core.support", "Support functions"),
    ("repro.core.reach", "Reachability"),
    ("repro.serve.engine", "Serving engines"),
    ("repro.serve.loadgen", "Open-loop load generation"),
    ("repro.runtime.autotune", "Cost-model autotuner"),
    ("repro.runtime.roofline", "Analytic roofline model"),
    ("repro.runtime.chaos", "Fault injection (chaos testing)"),
    ("repro.runtime.straggler", "Straggler-speculation scheduler"),
    ("repro.runtime.compile_cache", "Persistent compilation cache"),
    ("repro.runtime.trace", "Program spans (profiler tracing)"),
]

HEADER = """# API reference

*Generated by [`tools/gen_api_docs.py`](../tools/gen_api_docs.py) —
edit docstrings, not this file, then re-run*
`PYTHONPATH=src python tools/gen_api_docs.py`.
"""


def _public_members(mod):
    names = getattr(mod, "__all__", None)
    if names is None:
        names = [n for n in vars(mod) if not n.startswith("_")]
    out = []
    for n in names:
        obj = getattr(mod, n, None)
        if obj is None or inspect.ismodule(obj):
            continue
        if getattr(obj, "__module__", mod.__name__) != mod.__name__:
            continue  # re-exports documented at their home module
        if not (inspect.isfunction(obj) or inspect.isclass(obj)):
            continue
        out.append((n, obj))
    return out


def _signature(obj) -> str:
    try:
        sig = str(inspect.signature(obj))
    except (TypeError, ValueError):
        return "(...)"
    # function-object defaults (e.g. ``mv=matvec``) repr with a memory
    # address; strip it so regeneration is deterministic
    return re.sub(r" at 0x[0-9a-f]+", "", sig)


def _doc(obj) -> str:
    return inspect.getdoc(obj) or "*(undocumented)*"


def _render_class(name, cls) -> list[str]:
    lines = [f"### `{name}`", "", _doc(cls), ""]
    if dataclasses.is_dataclass(cls):
        rows = []
        for f in dataclasses.fields(cls):
            default = (
                "—" if f.default is dataclasses.MISSING else repr(f.default)
            )
            rows.append(f"| `{f.name}` | `{default}` |")
        if rows:
            lines += ["| field | default |", "| --- | --- |", *rows, ""]
    for mname, meth in inspect.getmembers(cls):
        if mname.startswith("_"):
            continue
        func = inspect.unwrap(getattr(meth, "__func__", meth))
        if not inspect.isfunction(func):
            continue
        if func.__qualname__.split(".")[0] != cls.__name__:
            continue  # inherited
        lines += [
            f"#### `{name}.{mname}{_signature(func)}`",
            "",
            _doc(func),
            "",
        ]
    return lines


def generate() -> str:
    parts = [HEADER]
    for modname, title in MODULES:
        mod = importlib.import_module(modname)
        parts += [f"\n## {title} — `{modname}`", "", _doc(mod), ""]
        for name, obj in _public_members(mod):
            if inspect.isclass(obj):
                parts += _render_class(name, obj)
            else:
                fn = inspect.unwrap(obj)
                parts += [f"### `{name}{_signature(fn)}`", "", _doc(fn), ""]
    return "\n".join(parts).rstrip() + "\n"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true", help="fail if stale")
    args = ap.parse_args()
    out = Path(__file__).resolve().parent.parent / "docs" / "api.md"
    text = generate()
    if args.check:
        if not out.exists() or out.read_text() != text:
            print(f"{out} is stale — regenerate with tools/gen_api_docs.py")
            return 1
        print("docs/api.md is current")
        return 0
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(text)
    print(f"wrote {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
