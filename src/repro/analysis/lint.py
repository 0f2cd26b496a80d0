"""Mode 1 driver: parse ``src/repro``, build the jit-reachability
graph, run every rule, filter through the allowlist.

The reachability graph is what makes QF201/QF301 repo-aware rather
than a grep: a function is *jit-reachable* when tracing can enter it —

* **R1** it is decorated with a tracing transform (``@jax.jit``,
  ``@partial(jax.jit, ...)``, ``shard_map``, ``custom_vjp``, ...);
* **R2** it is passed by name (or as a lambda) into a transform call
  (``jax.jit(f)``, ``lax.scan(body, ...)``, ``jax.grad``,
  ``eval_shape``, ``defvjp``, ...);
* **R3** it follows the repo's traced-function naming conventions in a
  *library* module (``*_apply``, ``*loss*``, ``step``, ``reset``,
  agent policies) — these are called through env/agent structs, which
  a static call graph cannot see;
* plus transitive closure over calls: names resolved through lexical
  scope, module scope and imports, and attribute calls name-matched
  into library modules only (driver modules — ``launch/``, ``serve/``
  — host orchestration code like latency timing that must never be
  flagged as traced unless it enters via R1/R2).
"""
from __future__ import annotations

import ast
import dataclasses
import os
from typing import Dict, List, Optional, Set, Tuple

from repro.analysis.rules import (Finding, RULES, FileCtx, FuncInfo,
                                  LintContext, build_file_ctx,
                                  dotted_name, resolve_dotted)

# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class LintConfig:
    # QF101: quantized data-path modules that must route contractions
    # through the blessed entry points
    # nn/conv.py is *scoped* (not blessed) since the Pallas/taps qconv
    # became the fxp8 default: its only remaining raw contractions are
    # the documented fp fallback + STE backward (see docs/kernels.md
    # "When to fall back to XLA"), each carrying an allowlist entry.
    qf101_scope: Tuple[str, ...] = (
        "src/repro/rl/", "src/repro/serve/", "src/repro/nn/linear.py",
        "src/repro/nn/conv.py",
    )
    qf101_blessed: Tuple[str, ...] = (
        "src/repro/core/qmatmul.py",
        "src/repro/core/vact.py", "src/repro/kernels/",
    )
    # QF501: modules implementing env wrappers
    qf501_scope: Tuple[str, ...] = (
        "src/repro/rl/envs/wrappers.py",
    )
    # QF601: driver CLIs exempt from the no-print rule — they are the
    # human-facing surface; everything else routes through repro.obs
    # (analysis/ is outside the lint universe already)
    qf601_exempt: Tuple[str, ...] = (
        "src/repro/launch/",
    )
    # library modules: naming conventions + attribute name-matching
    # may mark functions here as jit-reachable
    library: Tuple[str, ...] = (
        "src/repro/core/", "src/repro/nn/", "src/repro/rl/",
        "src/repro/kernels/", "src/repro/optim/",
        "src/repro/models/", "src/repro/distributed/",
        "src/repro/data/",
    )
    # rules to run (all by default)
    rules: Tuple[str, ...] = ()


TRANSFORMS = {
    "jax.jit", "jax.pmap", "jax.vmap", "jax.grad",
    "jax.value_and_grad", "jax.checkpoint", "jax.remat",
    "jax.custom_vjp", "jax.custom_jvp", "jax.eval_shape",
    "jax.make_jaxpr", "jax.linearize", "jax.jvp", "jax.vjp",
    "jax.shard_map",
    "jax.lax.scan", "jax.lax.while_loop", "jax.lax.fori_loop",
    "jax.lax.cond", "jax.lax.switch", "jax.lax.map",
    "jax.lax.associative_scan", "jax.lax.custom_root",
    "jax.tree_util.Partial",
}
PARTIAL_NAMES = {"functools.partial", "partial"}
# attribute calls that take traced callbacks positionally
CALLBACK_ATTRS = {"defvjp", "defjvp"}
# attribute names too generic to name-match across modules
METHOD_DENYLIST = {
    "append", "extend", "get", "items", "keys", "values", "pop",
    "update", "setdefault", "copy", "add", "discard", "remove",
    "sort", "index", "count", "join", "split", "strip", "format",
    "startswith", "endswith", "lower", "upper", "replace", "encode",
    "decode", "read", "write", "close", "open", "flush", "mkdir",
    "exists", "tolist", "item", "block_until_ready", "astype",
    "reshape", "sum", "mean", "max", "min", "any", "all", "clip",
    "squeeze", "ravel", "flatten", "transpose", "at", "set",
    "dump", "dumps", "load", "loads", "render",
}
# R3 conventions: leaf names tracing enters through struct fields
CONVENTION_EXACT = {"step", "reset", "greedy", "sampled", "behave",
                    "init", "apply"}
CONVENTION_SUFFIX = ("_apply",)
CONVENTION_SUBSTR = ("loss",)


def _is_library(rel: str, cfg: LintConfig) -> bool:
    return any(rel == p or rel.startswith(p.rstrip("/") + "/")
               for p in cfg.library)


def _leaf(qualname: str) -> str:
    return qualname.split(".")[-1]


def _matches_convention(leaf: str) -> bool:
    if leaf in CONVENTION_EXACT:
        return True
    if any(leaf.endswith(s) for s in CONVENTION_SUFFIX):
        return True
    return any(s in leaf for s in CONVENTION_SUBSTR)


# ---------------------------------------------------------------------------
# file collection
# ---------------------------------------------------------------------------


def collect_files(root: str,
                  paths: Optional[List[str]] = None) -> List[FileCtx]:
    """Parse the lint universe.  ``paths`` (absolute or root-relative)
    overrides the default ``src/repro/**`` sweep — used by the fixture
    self-tests."""
    out: List[FileCtx] = []
    if paths is None:
        base = os.path.join(root, "src", "repro")
        paths = []
        for dirpath, dirnames, filenames in os.walk(base):
            dirnames.sort()
            # the checker does not lint itself
            if os.path.basename(dirpath) == "analysis" and \
                    os.path.dirname(dirpath) == base:
                dirnames[:] = []
                continue
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    paths.append(os.path.join(dirpath, fn))
    for p in paths:
        ap = p if os.path.isabs(p) else os.path.join(root, p)
        rel = os.path.relpath(ap, root).replace(os.sep, "/")
        module = _module_name(rel)
        with open(ap, "r", encoding="utf-8") as fh:
            src = fh.read()
        out.append(build_file_ctx(ap, rel, module, src))
    return out


def _module_name(rel: str) -> str:
    parts = rel.split("/")
    if parts[:1] == ["src"]:
        parts = parts[1:]
    if parts and parts[-1].endswith(".py"):
        parts[-1] = parts[-1][:-3]
    if parts and parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts)


# ---------------------------------------------------------------------------
# jit-reachability graph
# ---------------------------------------------------------------------------


class _Reach:
    def __init__(self, files: List[FileCtx], cfg: LintConfig):
        self.files = files
        self.cfg = cfg
        self.by_module: Dict[str, FileCtx] = {
            f.module: f for f in files}
        # leaf name -> [(file, qualname)] in library modules only
        self.lib_by_leaf: Dict[str, List[Tuple[FileCtx, str]]] = {}
        for f in files:
            if not _is_library(f.rel, cfg):
                continue
            for qn in f.functions:
                self.lib_by_leaf.setdefault(_leaf(qn), []).append(
                    (f, qn))
        # lambda node -> qualname per file
        self.node_qn: Dict[int, Tuple[FileCtx, str]] = {}
        for f in files:
            for qn, info in f.functions.items():
                self.node_qn[id(info.node)] = (f, qn)
        self.reachable: Set[Tuple[str, str]] = set()
        self.work: List[Tuple[FileCtx, str]] = []

    def mark(self, f: FileCtx, qn: str):
        key = (f.rel, qn)
        if key not in self.reachable and qn in f.functions:
            self.reachable.add(key)
            self.work.append((f, qn))

    # -- name resolution -------------------------------------------------
    def resolve_name(self, f: FileCtx, scope: Optional[FuncInfo],
                     name: str) -> Optional[Tuple[FileCtx, str]]:
        # lexical scope chain (nested defs)
        info = scope
        while info is not None:
            cand = f"{info.qualname}.<locals>.{name}"
            if cand in f.functions:
                return f, cand
            info = info.parent
        # module level (incl. methods of module-level classes is NOT
        # name-only reachable here; plain defs only)
        if name in f.functions:
            return f, name
        # imports: from repro.x import name / import repro.x as m
        target = f.imports.get(name)
        if target and target.startswith("repro."):
            mod, _, leaf = target.rpartition(".")
            other = self.by_module.get(mod)
            if other and leaf in other.functions:
                return other, leaf
            # "from repro.rl import rollout" style: target is a module
            other = self.by_module.get(target)
            if other:
                return None
        return None

    def resolve_attr(self, f: FileCtx, name: str) -> List[
            Tuple[FileCtx, str]]:
        """``x.foo`` / ``mod.foo`` call targets."""
        resolved = resolve_dotted(name, f.imports)
        if resolved.startswith("repro."):
            mod, _, leaf = resolved.rpartition(".")
            other = self.by_module.get(mod)
            if other and leaf in other.functions:
                return [(other, leaf)]
        leaf = name.rsplit(".", 1)[-1]
        if leaf in METHOD_DENYLIST:
            return []
        # struct-field dispatch (env.step, agent.behave, buf.sample):
        # name-match into library modules only
        return list(self.lib_by_leaf.get(leaf, []))

    # -- roots ------------------------------------------------------------
    def _decorator_is_transform(self, f: FileCtx,
                                dec: ast.AST) -> bool:
        if isinstance(dec, ast.Call):
            name = dotted_name(dec.func)
            if name is None:
                return False
            resolved = resolve_dotted(name, f.imports)
            if resolved in TRANSFORMS:
                return True
            if resolved in PARTIAL_NAMES and dec.args:
                inner = dotted_name(dec.args[0])
                return bool(inner) and resolve_dotted(
                    inner, f.imports) in TRANSFORMS
            return False
        name = dotted_name(dec)
        return bool(name) and resolve_dotted(
            name, f.imports) in TRANSFORMS

    def seed(self):
        for f in self.files:
            # R1: transform decorators
            for qn, info in f.functions.items():
                node = info.node
                if not isinstance(node, ast.Lambda):
                    for dec in node.decorator_list:
                        if self._decorator_is_transform(f, dec):
                            self.mark(f, qn)
                # R3: naming conventions in library modules
                if _is_library(f.rel, self.cfg) and \
                        _matches_convention(_leaf(qn)):
                    self.mark(f, qn)
            # R2: functions passed into transform calls, anywhere
            for node in ast.walk(f.tree):
                if not isinstance(node, ast.Call):
                    continue
                name = dotted_name(node.func)
                is_transform = False
                if name is not None:
                    resolved = resolve_dotted(name, f.imports)
                    is_transform = (
                        resolved in TRANSFORMS
                        or name.rsplit(".", 1)[-1] in CALLBACK_ATTRS
                        or (resolved in PARTIAL_NAMES and node.args
                            and (inner := dotted_name(node.args[0]))
                            is not None
                            and resolve_dotted(inner, f.imports)
                            in TRANSFORMS))
                if not is_transform:
                    continue
                scope = self._enclosing_scope(f, node)
                for arg in list(node.args) + [kw.value for kw in
                                              node.keywords]:
                    if isinstance(arg, ast.Lambda):
                        hit = self.node_qn.get(id(arg))
                        if hit:
                            self.mark(*hit)
                    elif isinstance(arg, ast.Name):
                        hit = self.resolve_name(f, scope, arg.id)
                        if hit:
                            self.mark(*hit)

    def _enclosing_scope(self, f: FileCtx,
                         node: ast.AST) -> Optional[FuncInfo]:
        # cheapest correct option: find the innermost FuncInfo whose
        # subtree contains the node
        best, best_depth = None, -1
        for qn, info in f.functions.items():
            depth = qn.count(".")
            if depth <= best_depth:
                continue
            for sub in ast.walk(info.node):
                if sub is node:
                    best, best_depth = info, depth
                    break
        return best

    # -- propagation -------------------------------------------------------
    def propagate(self):
        while self.work:
            f, qn = self.work.pop()
            info = f.functions[qn]
            for node in ast.walk(info.node):
                # nested defs have their own reachability entries;
                # tracing falls through into them only via calls
                if not isinstance(node, ast.Call):
                    continue
                name = dotted_name(node.func)
                if name is None:
                    continue
                if "." in name:
                    for hit in self.resolve_attr(f, name):
                        self.mark(*hit)
                else:
                    hit = self.resolve_name(f, info, name)
                    if hit:
                        self.mark(*hit)


def build_reachability(files: List[FileCtx],
                       cfg: LintConfig) -> Set[Tuple[str, str]]:
    r = _Reach(files, cfg)
    r.seed()
    r.propagate()
    return r.reachable


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def run_lint(root: str, paths: Optional[List[str]] = None,
             config: Optional[LintConfig] = None) -> List[Finding]:
    cfg = config or LintConfig()
    files = collect_files(root, paths)
    ctx = LintContext(root=root, files=files, config=cfg)
    ctx.reachable = build_reachability(files, cfg)
    findings: List[Finding] = []
    active = cfg.rules or tuple(sorted(RULES))
    for rule_id in active:
        findings.extend(RULES[rule_id].check(ctx))
    findings.sort(key=lambda fd: (fd.path, fd.line, fd.rule))
    return findings
