#!/usr/bin/env python
"""Import-layering lint: the dependency order the PR-5/PR-7 refactors fixed.

Two rules, enforced over every module in ``src/repro`` by AST inspection
(no imports are executed):

1. **Layer order** -- module-level imports must point strictly *downward*:

       obs < configs < compression < kernels
           < {sim, metrics, distributed} < models
           < data < datagen < core < train < serving < launch

   ``obs`` (telemetry: span tracer, metrics registry, JAX profiling hooks)
   is the ladder's bottom rung: every layer may import it, and it imports
   nothing from ``repro`` at all (only the stdlib and ``jax``).

   Function-local (lazy) imports are the sanctioned escape hatch for the
   few documented back-edges -- compression -> kernels (backend dispatch),
   distributed.sharding -> train.optimizer (AdamState re-export),
   core.ensemble / train.checkpoint cross-links -- because they defer the
   dependency to call time and cannot create import cycles.  In particular
   ``core/`` never imports ``train/`` or ``serving/`` at module level.

2. **Codec seam** -- outside ``compression/`` and ``kernels/`` (the seam's
   implementation), no module imports ``repro.compression.transform`` /
   ``repro.compression.zfp`` or the mode-specific encode/decode free
   functions.  Everything goes through ``get_codec`` / the tree-codec API
   (``encode_tree`` / ``decode_tree``) so every consumer picks up new
   codecs, backends and wrappers (e.g. ``fixed_accuracy+residual``) for
   free.

Run directly (``python tools/check_layering.py``) or via
tests/test_layering.py; exits non-zero listing violations.
"""
from __future__ import annotations

import ast
import os
import sys
from typing import List, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src", "repro")

LAYER_RANK = {
    "obs": 0,                    # telemetry: stdlib + jax, importable anywhere
    "configs": 1,
    "compression": 2,
    "kernels": 3,
    "sim": 4, "metrics": 4, "distributed": 4,
    "models": 5,                 # the surrogate embeds sim constants
    "data": 6,
    "datagen": 7,
    "core": 8,
    "train": 9,
    "serving": 10,
    "launch": 11,
}

# the seam's internals: only compression/ and kernels/ may touch them
SEAM_PRIVATE_MODULES = ("repro.compression.transform", "repro.compression.zfp")
SEAM_PRIVATE_NAMES = frozenset({
    "encode_fixed_accuracy", "encode_fixed_accuracy_batch",
    "encode_fixed_rate", "encode_fixed_rate_batch",
    "decode_fixed_rate", "decode", "decode_batch",
    "blockify", "deblockify",
})
SEAM_EXEMPT_LAYERS = ("compression", "kernels")


def _layer_of(module: str) -> str | None:
    """'repro.data.store' -> 'data'; top-level modules map to their stem."""
    parts = module.split(".")
    if parts[0] != "repro" or len(parts) < 2:
        return None
    return parts[1] if parts[1] in LAYER_RANK else None


def _module_level_imports(tree: ast.Module):
    """(node, is_module_level) for every import; imports nested in a function
    body are lazy and exempt from the layer-order rule."""
    lazy_nodes = set()
    for fn in ast.walk(tree):
        if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            for sub in ast.walk(fn):
                if isinstance(sub, (ast.Import, ast.ImportFrom)):
                    lazy_nodes.add(id(sub))
    for node in ast.walk(tree):
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            yield node, id(node) not in lazy_nodes


def _imported_modules(node) -> List[str]:
    if isinstance(node, ast.Import):
        return [a.name for a in node.names]
    if node.level:                                 # relative import
        return []                                  # repro uses absolute only
    return [node.module] if node.module else []


def check(src_root: str = SRC) -> List[str]:
    violations: List[str] = []
    base = os.path.dirname(os.path.abspath(src_root))   # .../src
    for dirpath, _dirnames, filenames in os.walk(src_root):
        for fname in sorted(filenames):
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            rel = os.path.relpath(path, base)
            module = rel[:-3].replace(os.sep, ".").removesuffix(".__init__")
            layer = _layer_of(module)
            with open(path) as f:
                tree = ast.parse(f.read(), filename=rel)

            for node, module_level in _module_level_imports(tree):
                targets = _imported_modules(node)

                # rule 2: codec seam (module-level AND lazy: a lazy bypass
                # is still a bypass)
                if layer not in SEAM_EXEMPT_LAYERS:
                    for tgt in targets:
                        if tgt.startswith(SEAM_PRIVATE_MODULES):
                            violations.append(
                                f"{rel}:{node.lineno}: imports seam-private "
                                f"module {tgt} (use repro.compression / "
                                f"get_codec)")
                    if (isinstance(node, ast.ImportFrom) and node.module
                            and node.module.startswith("repro.compression")):
                        bad = sorted(a.name for a in node.names
                                     if a.name in SEAM_PRIVATE_NAMES)
                        if bad:
                            violations.append(
                                f"{rel}:{node.lineno}: imports mode-specific "
                                f"codec function(s) {', '.join(bad)} (use "
                                f"get_codec / encode_tree / decode_tree)")

                # rule 1: layer order, module-level only
                if not module_level or layer is None:
                    continue
                for tgt in targets:
                    tgt_layer = _layer_of(tgt)
                    if tgt_layer is None or tgt_layer == layer:
                        continue
                    if LAYER_RANK[tgt_layer] >= LAYER_RANK[layer]:
                        violations.append(
                            f"{rel}:{node.lineno}: layer '{layer}' "
                            f"(rank {LAYER_RANK[layer]}) imports layer "
                            f"'{tgt_layer}' (rank {LAYER_RANK[tgt_layer]}) "
                            f"at module level; import lazily or move the "
                            f"dependency down")
    return violations


def main() -> int:
    violations = check()
    if violations:
        print(f"{len(violations)} layering violation(s):")
        for v in violations:
            print(f"  {v}")
        return 1
    print("layering OK: "
          + " < ".join(sorted(LAYER_RANK, key=LAYER_RANK.get)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
