"""RPA004: x64 hygiene.

The jit PON backend does float64 queue arithmetic under a *scoped*
``jax.enable_x64(True)`` context (DESIGN §11); the ambient
``jax_enable_x64`` flag is never flipped, because an ambient flip
changes dtypes (and therefore bits) for every other jitted program in
the process — including the traffic sampler's pinned uint32/float32
streams.  This rule flags every ambient flip:

* ``jax.config.update("jax_enable_x64", ...)`` (any alias of
  ``jax.config`` / ``from jax import config``);
* attribute assignment ``jax.config.jax_enable_x64 = ...``;
* ``os.environ["JAX_ENABLE_X64"] = ...`` / ``putenv``.

Reads of the flag and the scoped ``jax.enable_x64(True)`` context
manager are allowed.
"""

from __future__ import annotations

import ast
from typing import Iterator

from repro.analysis.core import (
    Checker,
    Finding,
    ModuleInfo,
    dotted_name,
    enclosing_symbols,
)


def _const_str(node: ast.AST) -> str:
    return node.value if isinstance(node, ast.Constant) and isinstance(
        node.value, str
    ) else ""


class X64HygieneChecker(Checker):
    code = "RPA004"
    name = "x64-hygiene"
    description = (
        "the ambient jax_enable_x64 flag must never be flipped — use the "
        "scoped jax.enable_x64(True) context"
    )

    def check_module(self, mod: ModuleInfo) -> Iterator[Finding]:
        symbols = enclosing_symbols(mod.tree)
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call):
                fn = dotted_name(node.func) or ""
                if fn.endswith("config.update") or fn == "config.update":
                    if node.args and "x64" in _const_str(node.args[0]):
                        yield self.finding(
                            mod, node,
                            "ambient `config.update(\"jax_enable_x64\", …)` "
                            "— flip x64 only through the scoped "
                            "jax.enable_x64(True) context "
                            "(DESIGN §11 precision policy)",
                            symbols.get(node, "<module>"),
                        )
                elif fn in ("os.putenv",):
                    if node.args and "X64" in _const_str(node.args[0]):
                        yield self.finding(
                            mod, node,
                            "setting JAX_ENABLE_X64 via the environment "
                            "flips x64 process-wide — use the scoped "
                            "jax.enable_x64(True) context",
                            symbols.get(node, "<module>"),
                        )
            elif isinstance(node, ast.Assign):
                for target in node.targets:
                    dn = dotted_name(target) or ""
                    if dn.endswith("jax_enable_x64"):
                        yield self.finding(
                            mod, target,
                            "direct assignment to the ambient "
                            "jax_enable_x64 flag — use the scoped "
                            "jax.enable_x64(True) context",
                            symbols.get(node, "<module>"),
                        )
                    elif (
                        isinstance(target, ast.Subscript)
                        and (dotted_name(target.value) or "").endswith(
                            "environ"
                        )
                        and "X64" in _const_str(target.slice)
                    ):
                        yield self.finding(
                            mod, target,
                            "setting JAX_ENABLE_X64 via os.environ flips "
                            "x64 process-wide — use the scoped "
                            "jax.enable_x64(True) context",
                            symbols.get(node, "<module>"),
                        )
