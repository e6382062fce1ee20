"""tracer-leak pass — jitted bodies are pure; host math stays on host.

Two dual failure modes at the trace boundary, both invisible until a
bench regresses or a retrace detonates:

``tracer-leak``
    a jitted body (decorated def, or the ``impl`` behind a
    ``partial(jax.jit)(impl)`` assignment — discovered by the shared
    :mod:`tools.fusionlint.jitsites` scanner) writes to ``self.…``, a
    ``global``/``nonlocal``, or mutates one of them.  The write runs
    ONCE at trace time, not per call: a device value stored this way is
    a leaked tracer (``jax.errors.UnexpectedTracerError`` on a good
    day, silently stale state on a bad one), and even a host value is a
    trace-time constant masquerading as per-step state.  Retraces then
    observe whatever the attribute happens to hold — retrace
    determinism (the SPMD lockstep premise) is gone.

``host-jnp``
    a value built by a ``jnp.*`` call from purely host operands whose
    EVERY use is a host conversion (``int()`` / ``float()`` /
    ``np.asarray`` / ``.item()``/``.tolist()``) — host math routed
    through the accelerator: a device allocation, a kernel launch, and
    a blocking fetch to compute something ``numpy`` would do in
    nanoseconds inside the hot path.  Scoped to the host-sync hot-path
    table (``config.HOST_SYNC_MODULES``); detected with the dataflow
    layer's def-use chains.
"""

from __future__ import annotations

import ast

from tools.fusionlint import config
from tools.fusionlint.core import Finding, LintPass, Module
from tools.fusionlint.dataflow import (
    Prov,
    ProvenanceAnalysis,
    functions_of,
)
from tools.fusionlint.jitsites import scan_module

_MUTATORS = {"append", "extend", "add", "update", "insert", "pop",
             "setdefault", "clear", "remove", "discard"}
_HOST_CONV_CALLS = {"int", "float", "bool"}
_HOST_CONV_METHODS = {"item", "tolist"}


def _is_self_attr(expr: ast.expr) -> bool:
    cur = expr
    while isinstance(cur, (ast.Attribute, ast.Subscript)):
        cur = cur.value
    return isinstance(cur, ast.Name) and cur.id == "self"


class TracerLeakPass(LintPass):
    name = "tracer-leak"
    rules = ("tracer-leak", "host-jnp")

    def __init__(self,
                 scan_modules: list[str] | None = None,
                 hot_modules: dict[str, tuple[str, ...]] | None = None):
        self.scan_modules = (config.JIT_SCAN_MODULES
                             if scan_modules is None else scan_modules)
        self.hot_modules = (config.HOST_SYNC_MODULES
                            if hot_modules is None else hot_modules)
        self.analysis = ProvenanceAnalysis()

    def check_module(self, mod: Module) -> list[Finding]:
        findings: list[Finding] = []
        jitted: list[ast.AST] = []
        if mod.matches(self.scan_modules):
            jitted = scan_module(mod).jitted_bodies
            for body in jitted:
                findings.extend(self._check_jit_body(mod, body))
        if mod.rel in self.hot_modules:
            jit_ids = {id(b) for b in jitted}
            for func in functions_of(mod.tree):
                if id(func) in jit_ids:
                    continue
                findings.extend(self._check_host_jnp(mod, func))
        return findings

    # -- tracer-leak ----------------------------------------------------

    def _check_jit_body(self, mod: Module, body: ast.AST) -> list[Finding]:
        findings: list[Finding] = []
        fname = getattr(body, "name", "<jit>")
        for node in ast.walk(body):
            if isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = (node.targets if isinstance(node, ast.Assign)
                           else [node.target])
                for tgt in targets:
                    if isinstance(tgt, (ast.Attribute, ast.Subscript)) \
                            and _is_self_attr(tgt):
                        findings.append(Finding(
                            "tracer-leak", mod.rel, node.lineno,
                            f"jitted body {fname}() assigns to self.… — "
                            "the store runs once at trace time; a device "
                            "value here is a leaked tracer and retraces "
                            "silently observe stale state.  Return the "
                            "value instead"))
            elif isinstance(node, (ast.Global, ast.Nonlocal)):
                findings.append(Finding(
                    "tracer-leak", mod.rel, node.lineno,
                    f"jitted body {fname}() declares "
                    f"{'global' if isinstance(node, ast.Global) else 'nonlocal'} "
                    f"{', '.join(node.names)} — writes escape the trace "
                    "and run once at trace time, not per call"))
            elif (isinstance(node, ast.Call)
                  and isinstance(node.func, ast.Attribute)
                  and node.func.attr in _MUTATORS
                  and _is_self_attr(node.func.value)):
                findings.append(Finding(
                    "tracer-leak", mod.rel, node.lineno,
                    f"jitted body {fname}() mutates self.… via "
                    f".{node.func.attr}() — the mutation happens at trace "
                    "time only; traced values stored this way are leaked "
                    "tracers"))
        return findings

    # -- host-jnp -------------------------------------------------------

    def _check_host_jnp(self, mod: Module, func: ast.AST) -> list[Finding]:
        findings: list[Finding] = []
        du = self.analysis.analyze(func)
        for defs in du.defs.values():
            for d in defs:
                if not (isinstance(d.value, ast.Call)
                        and isinstance(d.value.func, ast.Attribute)
                        and isinstance(d.value.func.value, ast.Name)
                        and d.value.func.value.id == "jnp"):
                    continue
                # operands must be provably host-side
                operands = list(d.value.args) + [
                    kw.value for kw in d.value.keywords]
                provs = [self.analysis.prov_of(a, du, d.order)
                         for a in operands]
                if not provs or any(p in (Prov.DEVICE, Prov.UNKNOWN)
                                    for p in provs):
                    continue
                uses = du.uses_of(d)
                if not uses:
                    continue
                if all(self._is_host_conversion_use(u) for u in uses):
                    findings.append(Finding(
                        "host-jnp", mod.rel, d.node.lineno,
                        f"jnp.{d.value.func.attr}() on host-only operands "
                        f"whose result is only read back to host — a "
                        "device allocation + blocking fetch for math "
                        "numpy does in place; use np here"))
        return findings

    @staticmethod
    def _is_host_conversion_use(use) -> bool:
        call = use.call
        if call is None:
            return False
        f = call.func
        if isinstance(f, ast.Name) and f.id in _HOST_CONV_CALLS:
            return True
        if isinstance(f, ast.Attribute):
            if f.attr in _HOST_CONV_METHODS:
                return True
            if (f.attr == "asarray" and isinstance(f.value, ast.Name)
                    and f.value.id in ("np", "numpy")):
                return True
        return False
