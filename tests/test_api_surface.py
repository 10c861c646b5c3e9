"""Public-API surface gate (CI fast job).

The exported surface of every public package — names, function
signatures, class constructor + public-method signatures — is
snapshotted in tests/api_surface.txt. Any drift (a rename, a removed
export, a changed default) fails this test with a diff, so API changes
are always deliberate and reviewable in the same commit that makes
them.

Regenerate after an intentional change:

    REPRO_UPDATE_API_SURFACE=1 PYTHONPATH=src python -m pytest -q \
        tests/test_api_surface.py
"""

import importlib
import inspect
import os
import re

SNAPSHOT = os.path.join(os.path.dirname(__file__), "api_surface.txt")

# The import surfaces users consume: the package __init__s plus the
# serving submodules the DESIGN docs name as entry points.
PUBLIC_MODULES = [
    "repro",
    "repro.configs",
    "repro.core",
    "repro.data",
    "repro.kernels",
    "repro.models",
    "repro.quant",
    "repro.serving",
    "repro.sharding",
    "repro.training",
    "repro.utils",
]


def _sig(obj) -> str:
    """Signature with annotations stripped (they differ across Python
    versions) and memory addresses scrubbed from default reprs."""
    try:
        sig = inspect.signature(obj)
    except (ValueError, TypeError):
        return "(...)"
    parts, starred = [], False
    for p in sig.parameters.values():
        if p.name == "self":
            continue
        s = p.name
        if p.kind is p.VAR_POSITIONAL:
            s, starred = "*" + s, True
        elif p.kind is p.VAR_KEYWORD:
            s = "**" + s
        elif p.default is not p.empty:
            s += "=" + re.sub(r" at 0x[0-9a-f]+", "", repr(p.default))
        if p.kind is p.KEYWORD_ONLY and not starred:
            parts.append("*")
            starred = True
        parts.append(s)
    return "(" + ", ".join(parts) + ")"


def _describe(name: str, obj) -> list:
    if inspect.isclass(obj):
        lines = [f"class {name}{_sig(obj)}"]
        for mname, m in sorted(vars(obj).items()):
            if mname.startswith("_"):
                continue
            if isinstance(m, property):
                lines.append(f"  {name}.{mname} [property]")
            elif isinstance(m, staticmethod):
                lines.append(f"  {name}.{mname}"
                             f"{_sig(m.__func__)} [static]")
            elif isinstance(m, classmethod):
                lines.append(f"  {name}.{mname}"
                             f"{_sig(m.__func__)} [classmethod]")
            elif inspect.isfunction(m):
                lines.append(f"  {name}.{mname}{_sig(m)}")
        return lines
    if callable(obj):
        return [f"def {name}{_sig(obj)}"]
    return [f"{name} [{type(obj).__name__}]"]


def _ours(obj) -> bool:
    """Defined in this package. Names re-exported from jax, typing or
    the stdlib (`Mesh`, `P`, `dataclass`, ...) carry their own module
    and are not snapshotted: their signatures follow the installed
    versions, not this repo. Plain data values (`ARCH_IDS`, `DTYPES`)
    have no module of their own and stay."""
    mod = getattr(obj, "__module__", None)
    return not isinstance(mod, str) or mod.startswith("repro")


def _exports(mod) -> list:
    if hasattr(mod, "__all__"):
        names = mod.__all__
    else:
        names = [n for n, v in vars(mod).items()
                 if not n.startswith("_") and not inspect.ismodule(v)
                 and n != "annotations"]   # __future__ import leak
    return sorted(n for n in names if _ours(getattr(mod, n)))


def build_surface() -> str:
    out = []
    for modname in PUBLIC_MODULES:
        mod = importlib.import_module(modname)
        out.append(f"[{modname}]")
        for name in _exports(mod):
            out.extend(_describe(name, getattr(mod, name)))
        out.append("")
    return "\n".join(out).rstrip() + "\n"


def test_api_surface_matches_snapshot():
    current = build_surface()
    if os.environ.get("REPRO_UPDATE_API_SURFACE"):
        with open(SNAPSHOT, "w") as f:
            f.write(current)
        return
    assert os.path.exists(SNAPSHOT), (
        f"missing {SNAPSHOT}; generate it with "
        "REPRO_UPDATE_API_SURFACE=1")
    with open(SNAPSHOT) as f:
        committed = f.read()
    if current != committed:
        import difflib
        diff = "\n".join(difflib.unified_diff(
            committed.splitlines(), current.splitlines(),
            "api_surface.txt (committed)", "api_surface (current)",
            lineterm=""))
        raise AssertionError(
            "public API surface drifted from the committed snapshot.\n"
            "If intentional, regenerate with "
            "REPRO_UPDATE_API_SURFACE=1 and commit the diff.\n" + diff)
