#!/usr/bin/env python
"""Static collectives lint for the library tree.

The comm/compute-overlap PR's CI tripwire: raw device collectives in
library code bypass everything the kernels layer guarantees — the
quantized wire format, the size-adaptive algorithm selection, the
straight-through gradient convention, and the ``wire_bytes`` accounting
that keeps ``pt_collective_payload_bytes_total`` honest against the
compiled HLO.  One check over ``paddle_tpu/``:

  raw-collective   a call whose attribute name is ``ppermute`` or
                   ``psum`` (``lax.ppermute``, ``jax.lax.psum``, ...)
                   outside the sanctioned collective modules.  Route it
                   through ``kernels/ring_collectives.py`` /
                   ``kernels/quantized_collectives.py`` (or the op
                   lowerings in ``ops/collective_ops.py``) — or mark a
                   deliberate site with ``# collective: allow``.

  raw-sharding     a call to (or import of) ``NamedSharding``,
                   ``with_sharding_constraint`` or
                   ``custom_partitioning`` outside the sanctioned
                   sharding modules.  Sharding placement is POLICY: ad
                   hoc annotations scattered through library code bypass
                   the gspmd policy layer (`parallel/gspmd/specs.py`
                   named_sharding/constrain), drift from the mesh-axis
                   aliases, and make the resharding accounting
                   (`pt_gspmd_resharding_bytes`) unattributable.  Route
                   through the gspmd layer — or mark a deliberate site
                   with ``# collective: allow``.

Sanctioned modules (they ARE the collective surface):
``kernels/ring_collectives.py``, ``kernels/quantized_collectives.py``,
``kernels/pipeline_collectives.py`` (the pipeline lane's stage-boundary
shift/merge), ``ops/collective_ops.py``, plus — for both checks — the
gspmd core (``parallel/gspmd/specs|executor|quant_hook.py``; the
pipeline policy itself stays LINTED so its collectives must ride the
kernels surface or carry an explicit allow); the sharding check
additionally sanctions ``parallel/hybrid.py`` (its `_spec` is the
classic lane's one minting site).

Suppress a deliberate finding with ``# collective: allow`` on the same
line or the line above (e.g. the ring-attention kernel's own ppermute
ring, which rotates fp K/V blocks — payloads the quantized wire format
must not touch).  Exit 0 when clean, 1 with findings (one per line:
``path:lineno: [check] message``).  Walker/allow-mark/baseline
mechanics live in tools/lintlib.py.

Usage: python tools/lint_collectives.py [--baseline=FILE] [paths...]
  (no args = paddle_tpu/, repo-relative)
"""

from __future__ import annotations

import ast
import sys
from pathlib import Path

import lintlib

REPO = lintlib.REPO

DEFAULT_TARGETS = ["paddle_tpu"]

# the sanctioned collective surface — raw psum/ppermute is their job.
# NOTE: parallel/gspmd/pipeline_policy.py is deliberately NOT here — the
# pipeline island's stage-boundary ppermutes must route through
# kernels/pipeline_collectives.py (stage_shift/stage_merge, the
# boundary-bytes accounting), and its one exact-fp32 reduction carries
# an explicit `# collective: allow`.
EXEMPT = (
    "paddle_tpu/kernels/ring_collectives.py",
    "paddle_tpu/kernels/quantized_collectives.py",
    "paddle_tpu/kernels/pipeline_collectives.py",
    "paddle_tpu/ops/collective_ops.py",
    "paddle_tpu/parallel/gspmd/specs.py",
    "paddle_tpu/parallel/gspmd/executor.py",
    "paddle_tpu/parallel/gspmd/quant_hook.py",
)

# the sanctioned sharding-placement surface (raw-sharding check only)
EXEMPT_SHARDING = EXEMPT + (
    "paddle_tpu/parallel/hybrid.py",
)

RAW_COLLECTIVES = ("ppermute", "psum")

# sharding-placement constructs that must route through the gspmd layer
RAW_SHARDING = ("NamedSharding", "with_sharding_constraint",
                "custom_partitioning")

ALLOW_MARK = "collective: allow"


def _allowed(src_lines, lineno):
    """Marker accepted on the flagged line or the line directly above."""
    return lintlib.allowed(src_lines, lineno, ALLOW_MARK)


def _call_name(node):
    """The called name for a Call node: the attribute (lax.psum -> psum)
    or the bare name (NamedSharding(...) -> NamedSharding)."""
    if isinstance(node.func, ast.Attribute):
        return node.func.attr
    if isinstance(node.func, ast.Name):
        return node.func.id
    return None


def _rules(sharding_exempt):
    def raw_calls(node):
        if not isinstance(node, ast.Call):
            return
        name = _call_name(node)
        if isinstance(node.func, ast.Attribute) and name in RAW_COLLECTIVES:
            yield (node.lineno, "raw-collective",
                   f"raw {name}() outside the kernels layer — route "
                   "through kernels/ring_collectives.py (quantized wire "
                   "format, algorithm selection, wire-bytes accounting) "
                   f"or mark a deliberate site `# {ALLOW_MARK}`")
        elif not sharding_exempt and name in RAW_SHARDING:
            yield (node.lineno, "raw-sharding",
                   f"raw {name}() outside the gspmd layer — sharding "
                   "placement is policy: route through "
                   "parallel/gspmd/specs.py (named_sharding/constrain, "
                   "axis aliases, resharding accounting) or mark a "
                   f"deliberate site `# {ALLOW_MARK}`")

    def raw_imports(node):
        if not isinstance(node, ast.ImportFrom) or sharding_exempt:
            return
        for alias in node.names:
            if alias.name in RAW_SHARDING:
                yield (node.lineno, "raw-sharding",
                       f"import of {alias.name} outside the gspmd "
                       "layer — sharding placement is policy: route "
                       "through parallel/gspmd/specs.py or mark a "
                       f"deliberate site `# {ALLOW_MARK}`")

    return (raw_calls, raw_imports)


def check_source(src: str, path: str = "<string>",
                 sharding_exempt: bool = False):
    """Lint one file's source; returns [(path, lineno, check, message)]."""
    return lintlib.scan(src, path, _rules(sharding_exempt), ALLOW_MARK)


def _exempt(rel_str: str) -> bool:
    return rel_str in EXEMPT


def check_file(path: Path):
    rel_str = lintlib.rel_path(path)
    if _exempt(rel_str):
        return []
    return check_source(path.read_text(encoding="utf-8"), rel_str,
                        sharding_exempt=rel_str in EXEMPT_SHARDING)


def main(argv):
    argv, baseline = lintlib.split_baseline_arg(argv)
    targets = argv or DEFAULT_TARGETS
    findings = []
    for t in targets:
        p = (REPO / t) if not Path(t).is_absolute() else Path(t)
        files = sorted(p.rglob("*.py")) if p.is_dir() else [p]
        for f in files:
            findings.extend(check_file(f))
    findings = lintlib.apply_baseline(findings, baseline)
    lintlib.print_findings(findings)
    if findings:
        print(f"{len(findings)} finding(s)")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
