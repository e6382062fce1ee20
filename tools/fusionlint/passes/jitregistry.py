"""jit-registry pass — every trace boundary is enumerated, on purpose.

The compile-signature discipline PRs 4-6 built (pow2 rows, bucketed
flat axis, eager env resolution into static args) only holds if the
set of jitted entry points and their static/traced splits is a
*reviewed artifact*, not whatever the code happens to contain.  The
checked-in registry (``fusioninfer_tpu/utils/jit_registry.py``) is that
artifact; this pass diffs reality against it:

* a ``jax.jit`` / ``shard_map`` site the registry does not list —
  someone opened a new trace boundary without declaring its compile
  contract (or its budget family);
* a registry entry with no matching site — stale after a rename, and
  the compile ledger silently stops covering it;
* a static/traced split that differs from the registry — moving an
  argument across the boundary changes what mints compile signatures
  and is exactly the drift that turns a bounded family unbounded.

The registry file is pure data and is loaded by ``exec`` of its source
(never importing the package — lint must run in the pip-less image).
"""

from __future__ import annotations

import pathlib

from tools.fusionlint import config
from tools.fusionlint.core import REPO, Finding, LintPass, Module
from tools.fusionlint.jitsites import scan_module


def load_registry(path: pathlib.Path) -> dict[str, dict]:
    """ENTRY_POINTS from the registry module, loaded without importing
    the package (the file is pure data by contract)."""
    ns: dict = {"__name__": "jit_registry_data"}
    exec(compile(path.read_text(), str(path), "exec"), ns)  # noqa: S102
    return ns["ENTRY_POINTS"]


def entry_name(key: str) -> str:
    """Terminal callable name of a registry key:
    ``"m.py::make_x.init#shard_map"`` → ``init``.  The ONE place the
    key grammar is parsed — the dataflow passes build their
    device-callee sets through this."""
    return key.split("::", 1)[1].split(".")[-1].split("#")[0]


def load_budgets(path: pathlib.Path) -> dict[str, int]:
    ns: dict = {"__name__": "jit_registry_data"}
    exec(compile(path.read_text(), str(path), "exec"), ns)  # noqa: S102
    return ns["FAMILY_BUDGETS"]


class JitRegistryPass(LintPass):
    name = "jit-registry"
    rules = ("jit-registry",)

    def __init__(self,
                 registry_path: str | None = None,
                 scan_modules: list[str] | None = None):
        self.registry_rel = (config.JIT_REGISTRY_MODULE
                             if registry_path is None else registry_path)
        path = pathlib.Path(self.registry_rel)
        if not path.is_absolute():
            path = REPO / path
        self.registry_path = path
        try:
            self.registry = load_registry(path)
        except (OSError, SyntaxError, KeyError):
            self.registry = None  # reported in finalize
        self.scan_modules = (config.JIT_SCAN_MODULES
                             if scan_modules is None else scan_modules)

    def finalize(self, modules: list[Module]) -> list[Finding]:
        if self.registry is None:
            return [Finding(
                "jit-registry", self.registry_rel, 1,
                "jit registry module is missing or unparseable — the "
                "entry-point contract cannot be checked")]
        findings: list[Finding] = []
        seen: dict[str, tuple[Module, int]] = {}
        scan = [m for m in modules if m.matches(self.scan_modules)]
        # --changed safety: editing the registry FILE can invalidate
        # entries whose sites live in files outside the changed set (a
        # deleted entry's site, a retyped split).  When the registry
        # module itself is in the linted set, widen to the full package
        # so the diff gate cannot pass on a registry-only edit that
        # drifts from unchanged code.
        if any(m.rel == self.registry_rel for m in modules):
            have = {m.rel for m in scan}
            roots = sorted({g.split("*", 1)[0].rstrip("/")
                            for g in self.scan_modules if "*" in g
                            and g.split("*", 1)[0]})
            from tools.fusionlint.core import collect_files
            for f in collect_files(roots):
                extra = Module(f)
                if (extra.rel in have or extra.tree is None
                        or not extra.matches(self.scan_modules)):
                    continue
                scan.append(extra)
        for mod in scan:
            for key, site in scan_module(mod).sites.items():
                seen[key] = (mod, site.line)
                entry = self.registry.get(key)
                if entry is None:
                    findings.append(Finding(
                        "jit-registry", mod.rel, site.line,
                        f"{site.kind} entry point {key.split('::', 1)[1]!r} "
                        f"is not in {self.registry_rel} — declare its "
                        "family and static/traced split (every trace "
                        "boundary is a reviewed artifact)"))
                    continue
                if entry.get("kind") != site.kind:
                    findings.append(Finding(
                        "jit-registry", mod.rel, site.line,
                        f"{key.split('::', 1)[1]!r} is registered as "
                        f"{entry.get('kind')!r} but the code says "
                        f"{site.kind!r} — update {self.registry_rel}"))
                if site.kind == "jit":
                    want_nums = tuple(entry.get("static_argnums", ()))
                    want_names = tuple(entry.get("static_argnames", ()))
                    if (site.static_argnums != want_nums
                            or site.static_argnames != want_names):
                        findings.append(Finding(
                            "jit-registry", mod.rel, site.line,
                            f"static split of {key.split('::', 1)[1]!r} "
                            f"drifted from {self.registry_rel}: code has "
                            f"argnums={site.static_argnums} "
                            f"argnames={site.static_argnames}, registry "
                            f"has argnums={want_nums} "
                            f"argnames={want_names} — moving an argument "
                            "across the trace boundary changes what "
                            "mints compile signatures"))
        # stale registry entries (only when the scan actually covered
        # the package — a path-scoped run must not call entries stale)
        scanned = {m.rel for m in scan}
        for key in self.registry:
            rel = key.split("::", 1)[0]
            if rel in scanned and key not in seen:
                line = self._registry_line(key)
                findings.append(Finding(
                    "jit-registry", self.registry_rel, line,
                    f"registry entry {key!r} matches no jit/shard_map "
                    "site — stale after a rename? (the compile ledger "
                    "silently stops covering it)"))
        return findings

    def _registry_line(self, key: str) -> int:
        try:
            for i, text in enumerate(
                    self.registry_path.read_text().splitlines(), 1):
                if f'"{key}"' in text or f"'{key}'" in text:
                    return i
        except OSError:
            pass
        return 1
