"""Pallas parity discipline: every jit-reachable ``pl.pallas_call``
site must be pinned by an interpret-mode parity test.

The repo's Pallas kernels only run natively on the accelerator; CI is
CPU-only and exercises them through ``interpret=True``.  The ONLY thing
standing between a fused kernel and a silent bitwise divergence from
the reference fold is the interpret-mode parity test that compares the
two — so that pin is a contract, not a courtesy.  Each module that owns
a pallas_call declares a literal registry::

    PALLAS_PARITY_TESTS = {
        "combat_fold_pallas": "tests/test_stencil_pallas.py",
    }

mapping the enclosing function name to the test file that pins it.  The
rule walks the jit-reachable call graph (same roots as trace-safety),
finds every reachable pallas_call, and checks the registry names its
enclosing function, the named file exists, and the file's text actually
mentions both the function and ``interpret`` (a registry pointing at an
unrelated file is as good as no registry).  Stale registry keys — a
kernel renamed or deleted without updating its pin — are findings too,
so the registry tracks reality in both directions.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from .callgraph import traced_reachable
from .engine import Finding, PackageContext, Rule, dotted_name

REGISTRY_NAME = "PALLAS_PARITY_TESTS"

#: the registry file must contain this word: a parity test that never
#: runs the kernel in interpret mode proves nothing on a CPU CI image
INTERPRET_MARKER = "interpret"


def _literal_registry(tree) -> Optional[Tuple[int, Dict[str, str]]]:
    """The module's top-level ``PALLAS_PARITY_TESTS`` literal, if any.

    Only str->str constant dicts count: a computed registry can't be
    audited statically, which defeats the point of the pin.
    """
    for node in tree.body:
        targets = []
        if isinstance(node, ast.Assign):
            targets = node.targets
            value = node.value
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets = [node.target]
            value = node.value
        else:
            continue
        for tgt in targets:
            if isinstance(tgt, ast.Name) and tgt.id == REGISTRY_NAME:
                if not isinstance(value, ast.Dict):
                    return node.lineno, {}
                out: Dict[str, str] = {}
                for k, v in zip(value.keys, value.values):
                    if isinstance(k, ast.Constant) \
                            and isinstance(k.value, str) \
                            and isinstance(v, ast.Constant) \
                            and isinstance(v.value, str):
                        out[k.value] = v.value
                return node.lineno, out
    return None


class PallasParityPinnedRule(Rule):
    """Every jit-reachable pallas_call is named by an interpret-mode
    parity test via its module's ``PALLAS_PARITY_TESTS`` registry."""

    name = "pallas-parity-pinned"
    description = (
        "Each jit-reachable pl.pallas_call's enclosing function must "
        "appear in its module's literal PALLAS_PARITY_TESTS registry, "
        "pointing at an existing test file whose text names the "
        "function and runs it in interpret mode; stale registry keys "
        "are findings too.")
    per_module = False

    def run_package(self, ctx: PackageContext) -> List[Finding]:
        self.findings = []
        # rel -> {func name -> first pallas_call line}
        callers: Dict[str, Dict[str, int]] = {}
        for tf in traced_reachable(ctx).values():
            if tf.info.rel not in ctx.modules:
                continue
            for node in ast.walk(tf.info.node):
                if not isinstance(node, ast.Call):
                    continue
                d = dotted_name(node.func)
                if d is None or d.split(".")[-1] != "pallas_call":
                    continue
                per = callers.setdefault(tf.info.rel, {})
                name = tf.info.qual.rsplit(".", 1)[-1]
                per.setdefault(name, node.lineno)

        for rel in sorted(callers):
            self.module = ctx.modules[rel]
            reg = _literal_registry(self.module.tree)
            for fname, line in sorted(callers[rel].items()):
                if reg is None:
                    self.flag(line, "jit-reachable pallas_call in "
                              f"`{fname}` but the module declares no "
                              f"literal {REGISTRY_NAME} registry — the "
                              "kernel has no interpret-mode parity pin",
                              path=rel)
                    continue
                _, entries = reg
                if fname not in entries:
                    self.flag(line, f"jit-reachable pallas_call in "
                              f"`{fname}` is not named in "
                              f"{REGISTRY_NAME} — no interpret-mode "
                              "parity test pins this kernel",
                              path=rel)
                    continue
                self._check_pin(ctx, rel, line, fname, entries[fname])

        # stale keys: a registry entry whose kernel vanished (renamed,
        # deleted, or no longer jit-reachable) is a pin guarding nothing
        for rel, mod in ctx.modules.items():
            if mod.tree is None:
                continue
            reg = _literal_registry(mod.tree)
            if reg is None:
                continue
            reg_line, entries = reg
            live: Set[str] = set(callers.get(rel, ()))
            self.module = mod
            for fname in sorted(set(entries) - live):
                self.flag(reg_line, f"{REGISTRY_NAME} entry `{fname}` "
                          "matches no jit-reachable pallas_call in this "
                          "module — stale pin (kernel renamed, deleted, "
                          "or unrooted)", path=rel)
        return self.findings

    def _check_pin(self, ctx: PackageContext, rel: str, line: int,
                   fname: str, pin: str) -> None:
        # pins resolve against the scan root first (fixture layouts),
        # then its parent (the real tree: root is the package dir and
        # tests/ is its sibling)
        for base in (ctx.root, ctx.root.parent):
            path = base / pin
            if path.is_file():
                break
        else:
            self.flag(line, f"{REGISTRY_NAME} pins `{fname}` to "
                      f"`{pin}`, which does not exist — the parity "
                      "test has vanished", path=rel)
            return
        try:
            text = path.read_text(encoding="utf-8")
        except OSError:
            self.flag(line, f"{REGISTRY_NAME} pin `{pin}` for "
                      f"`{fname}` is unreadable", path=rel)
            return
        if fname not in text:
            self.flag(line, f"parity pin `{pin}` never mentions "
                      f"`{fname}` — the registry points at a file that "
                      "does not test this kernel", path=rel)
        elif INTERPRET_MARKER not in text:
            self.flag(line, f"parity pin `{pin}` for `{fname}` never "
                      f"uses `{INTERPRET_MARKER}` mode — on the CPU CI "
                      "image the kernel is only exercised through "
                      "interpret=True, so this pins nothing", path=rel)
