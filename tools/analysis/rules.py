"""The repro-lint rules. Each rule is `fn(path, tree, lines) -> [Finding]`.

These are deliberately CODEBASE-SPECIFIC: every rule encodes a contract
this repo already broke once (see tools/analysis/__init__ for the
history). They under-approximate — a finding is near-certainly real; a
clean run is not a proof — which is the right trade for an enforced CI
gate.
"""
from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from tools.analysis.core import Finding

# --------------------------------------------------------------- helpers
def dotted(node: ast.AST) -> str:
    """'jax.lax.dot_general' for nested Attribute/Name chains, '' when
    the node is not a plain dotted name."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def tail(node: ast.AST) -> str:
    """Last segment of a dotted callee ('mha' for repro...ops.mha)."""
    d = dotted(node)
    return d.rsplit(".", 1)[-1] if d else ""


def parent_map(root: ast.AST) -> Dict[ast.AST, ast.AST]:
    out: Dict[ast.AST, ast.AST] = {}
    for parent in ast.walk(root):
        for child in ast.iter_child_nodes(parent):
            out[child] = parent
    return out


def enclosing_functions(tree: ast.AST):
    """Yield (funcdef, [ancestor names]) for every def in the module."""
    def walk(node, stack):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield child, list(stack)
                yield from walk(child, stack + [child.name])
            else:
                yield from walk(child, stack)
    yield from walk(tree, [])


def _const_strs(node: ast.AST) -> Optional[List[str]]:
    """static_argnames value -> list of names (string or tuple/list of
    strings), None when not statically resolvable."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
        out = []
        for el in node.elts:
            if not (isinstance(el, ast.Constant) and isinstance(el.value, str)):
                return None
            out.append(el.value)
        return out
    return None


# ------------------------------------------------------ RL001 recompile
_SHAPE_ATTRS = {"shape", "ndim", "dtype", "size", "sharding"}
_BENIGN_CALLS = {"len", "isinstance", "hasattr", "getattr", "type", "callable"}


def _jit_static(dec_list) -> Optional[Tuple[Optional[List[str]], List[int]]]:
    """None when the decorator list has no jit; else (static_argnames or
    None-if-unresolvable, static_argnums)."""
    for dec in dec_list:
        if dotted(dec) in ("jax.jit", "jit"):
            return [], []
        if isinstance(dec, ast.Call):
            f = dotted(dec.func)
            if f in ("jax.jit", "jit"):
                return _jit_call_static(dec)
            if f in ("functools.partial", "partial") and dec.args and \
                    dotted(dec.args[0]) in ("jax.jit", "jit"):
                return _jit_call_static(dec)
    return None


def _jit_call_static(call: ast.Call):
    names: Optional[List[str]] = []
    nums: List[int] = []
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            names = _const_strs(kw.value)
        elif kw.arg == "static_argnums":
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                nums = [v.value]
            elif isinstance(v, (ast.Tuple, ast.List)):
                nums = [
                    el.value for el in v.elts
                    if isinstance(el, ast.Constant)
                    and isinstance(el.value, int)
                ]
    return names, nums


def _param_names(fn) -> List[str]:
    a = fn.args
    return (
        [p.arg for p in a.posonlyargs]
        + [p.arg for p in a.args]
        + [p.arg for p in a.kwonlyargs]
    )


def _hazardous_refs(expr: ast.AST, traced: Set[str]) -> List[str]:
    """Names in `traced` used by VALUE inside `expr` — i.e. not through
    a shape/dtype attribute, `is None` test, or len()/isinstance()."""
    pm = parent_map(expr)
    pm[expr] = None  # type: ignore[assignment]
    bad: List[str] = []
    for node in ast.walk(expr):
        if not (isinstance(node, ast.Name) and node.id in traced):
            continue
        parent = pm.get(node)
        if isinstance(parent, ast.Attribute) and parent.attr in _SHAPE_ATTRS:
            continue
        if isinstance(parent, ast.Compare) and all(
            isinstance(op, (ast.Is, ast.IsNot)) for op in parent.ops
        ):
            continue
        if isinstance(parent, ast.Call) and node in parent.args and \
                tail(parent.func) in _BENIGN_CALLS:
            continue
        bad.append(node.id)
    return bad


def rl001(path: str, tree: ast.AST, lines: Sequence[str]) -> List[Finding]:
    out: List[Finding] = []
    jitted: List[Tuple[ast.FunctionDef, Optional[List[str]], List[int]]] = []
    for fn, _stack in enclosing_functions(tree):
        info = _jit_static(fn.decorator_list)
        if info is not None:
            jitted.append((fn, *info))

    # expression-form jit: f2 = jax.jit(f, static_argnames=...) — attach
    # to the def of the same name when it lives in this module
    defs_by_name = {}
    for fn, _stack in enclosing_functions(tree):
        defs_by_name.setdefault(fn.name, fn)
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and dotted(node.func) in ("jax.jit", "jit"):
            if node.args and isinstance(node.args[0], ast.Name):
                target = defs_by_name.get(node.args[0].id)
                if target is not None and _jit_static(target.decorator_list) is None:
                    jitted.append((target, *_jit_call_static(node)))

    for fn, static_names, static_nums in jitted:
        params = _param_names(fn)
        if params and params[0] in ("self", "cls"):
            params = params[1:]
        if static_names is None:
            # dynamically-built static_argnames: nothing checkable
            static_names = []
        for name in static_names:
            if name not in params:
                out.append(Finding(
                    "RL001", path, fn.lineno,
                    f"static_argnames entry {name!r} matches no parameter "
                    f"of `{fn.name}` — typo'd static args silently trace",
                ))
        static = set(static_names)
        for i in static_nums:
            if 0 <= i < len(params):
                static.add(params[i])

        # unhashable defaults on static params
        a = fn.args
        pos = [p.arg for p in a.posonlyargs] + [p.arg for p in a.args]
        pos_defaults = list(zip(pos[len(pos) - len(a.defaults):], a.defaults))
        kw_defaults = [
            (p.arg, d) for p, d in zip(a.kwonlyargs, a.kw_defaults)
            if d is not None
        ]
        for pname, dflt in pos_defaults + kw_defaults:
            if pname in static and isinstance(
                dflt, (ast.List, ast.Dict, ast.Set)
            ):
                out.append(Finding(
                    "RL001", path, dflt.lineno,
                    f"static arg {pname!r} of `{fn.name}` defaults to an "
                    f"unhashable {type(dflt).__name__.lower()} — jit "
                    f"static args must hash",
                ))
            if pname not in static and isinstance(dflt, ast.Constant) and \
                    isinstance(dflt.value, str):
                out.append(Finding(
                    "RL001", path, dflt.lineno,
                    f"string-valued arg {pname!r} of jit'd `{fn.name}` is "
                    f"not in static_argnames — strings cannot trace",
                ))

        traced = set(params) - static
        for node in ast.walk(fn):
            # nested defs re-binding a name shadow it out of `traced`
            if isinstance(node, (ast.If, ast.While)):
                refs = _hazardous_refs(node.test, traced)
                if refs:
                    out.append(Finding(
                        "RL001", path, node.lineno,
                        f"`{'if' if isinstance(node, ast.If) else 'while'}`"
                        f" branches on traced value(s) "
                        f"{', '.join(sorted(set(refs)))} inside jit'd "
                        f"`{fn.name}` — recompile per value (or trace "
                        f"error); hoist to static_argnames or use "
                        f"lax.cond/jnp.where",
                    ))
            elif isinstance(node, ast.For):
                it = node.iter
                if isinstance(it, ast.Call) and tail(it.func) == "range":
                    refs = [
                        r for arg in it.args
                        for r in _hazardous_refs(arg, traced)
                    ]
                    if refs:
                        out.append(Finding(
                            "RL001", path, node.lineno,
                            f"`for` over range({', '.join(sorted(set(refs)))})"
                            f" inside jit'd `{fn.name}` unrolls/recompiles "
                            f"per traced length — use lax.fori_loop/scan",
                        ))
    return out


# -------------------------------------------------- RL002 bf16 accumulation
_DOT_CALLEES = {
    "jnp.dot", "jnp.matmul", "jnp.einsum", "jnp.tensordot",
    "jax.numpy.dot", "jax.numpy.matmul", "jax.numpy.einsum",
    "jax.lax.dot_general", "lax.dot_general", "jax.lax.dot", "lax.dot",
    "pl.dot",
}


def _is_f32_cast(node: ast.AST) -> bool:
    """`x.astype(jnp.float32)` / `jnp.float32(x)` / a float32 dtype ref."""
    if isinstance(node, ast.Call):
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr == "astype" and node.args:
            return dotted(node.args[0]).endswith("float32")
        return dotted(f).endswith("float32")
    return False


def rl002(path: str, tree: ast.AST, lines: Sequence[str]) -> List[Finding]:
    if "src/repro/kernels/" not in path:
        return []
    pm = parent_map(tree)
    out: List[Finding] = []

    def result_cast_f32(call: ast.Call) -> bool:
        p = pm.get(call)
        if isinstance(p, ast.Attribute) and p.attr == "astype":
            pp = pm.get(p)
            if isinstance(pp, ast.Call) and pp.args and \
                    dotted(pp.args[0]).endswith("float32"):
                return True
        return False

    for node in ast.walk(tree):
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.MatMult):
            out.append(Finding(
                "RL002", path, node.lineno,
                "`@` matmul in a kernel package cannot set "
                "preferred_element_type — use jnp.dot(..., "
                "preferred_element_type=jnp.float32)",
            ))
            continue
        if not isinstance(node, ast.Call):
            continue
        d = dotted(node.func)
        if d not in _DOT_CALLEES:
            continue
        if any(kw.arg == "preferred_element_type" for kw in node.keywords):
            continue
        if result_cast_f32(node):
            continue  # explicit fp32 cast on the result
        arr_args = [
            a for a in node.args
            if not (isinstance(a, ast.Constant) and isinstance(a.value, str))
        ]
        if arr_args and all(_is_f32_cast(a) for a in arr_args):
            continue  # all operands explicitly cast to fp32
        out.append(Finding(
            "RL002", path, node.lineno,
            f"{d} in a kernel package without "
            f"preferred_element_type=jnp.float32 or an explicit fp32 "
            f"cast — bf16 accumulation drifts (the PR 4 absorbed-MLA "
            f"bug class)",
        ))
    return out


# ------------------------------------------------ RL003 deprecated surface
# callee tail -> the kwargs deprecated on it. `interpret=` stays
# first-class on the RAW kernel entry points (moe_gemm, flash_attention,
# expert_ffn_gemv, paged_prefill_*/paged_decode_*) — only the unified
# op wrappers and the loop/engine constructors deprecated theirs.
DEPRECATED_KWARGS: Dict[str, Set[str]] = {
    "ServingLoop": {"plan_size", "thresholds"},
    "TriMoEServingEngine": {"plan_size", "thresholds"},
    "grouped_expert_matmul": {"interpret", "use_ref"},
    "grouped_expert_ffn": {"interpret", "use_ref"},
    "cold_expert_ffn": {"interpret", "use_ref"},
    "mha": {"interpret", "use_ref"},
    "moe_forward": {"interpret", "use_ref"},
}
_REPLACEMENT = {
    "plan_size": "scheduler=SchedulerPolicy(plan_size=...)",
    "thresholds": "scheduler=SchedulerPolicy(thresholds=...)",
    "interpret": 'backend="auto"|"pallas"|"ref"',
    "use_ref": 'backend="ref"',
}


def rl003(path: str, tree: ast.AST, lines: Sequence[str]) -> List[Finding]:
    out: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        dep = DEPRECATED_KWARGS.get(tail(node.func))
        if not dep:
            continue
        for kw in node.keywords:
            if kw.arg in dep:
                out.append(Finding(
                    "RL003", path, kw.value.lineno,
                    f"deprecated `{kw.arg}=` on {tail(node.func)}() — "
                    f"pass {_REPLACEMENT[kw.arg]}",
                ))
    return out


# --------------------------------------------------- RL004 stats bypass
_OBS_MODULES = ("repro.obs", "repro.obs.metrics")
_INSTRUMENT_CLASSES = {"Counter", "Gauge", "Histogram", "DerivedGauge"}


def rl004(path: str, tree: ast.AST, lines: Sequence[str]) -> List[Finding]:
    if path.startswith("src/repro/obs/") or path.startswith("tools/analysis/"):
        return []
    out: List[Finding] = []
    obs_names: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module in _OBS_MODULES:
            for alias in node.names:
                if alias.name in _INSTRUMENT_CLASSES:
                    obs_names.add(alias.asname or alias.name)
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and node.attr == "_metrics":
            out.append(Finding(
                "RL004", path, node.lineno,
                "private MetricsRegistry._metrics access — go through "
                "counter()/gauge()/histogram()/get()/snapshot()",
            ))
        elif isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
                and node.func.id in obs_names:
            out.append(Finding(
                "RL004", path, node.lineno,
                f"raw {node.func.id}(...) construction bypasses the "
                f"registry's get-or-create (aliasing + kind checks) — "
                f"use MetricsRegistry.{node.func.id.lower().replace('derivedgauge', 'derived')}()",
            ))
        elif isinstance(node, (ast.Assign, ast.AugAssign)):
            targets = node.targets if isinstance(node, ast.Assign) else [node.target]
            for t in targets:
                if isinstance(t, ast.Attribute) and t.attr == "samples":
                    out.append(Finding(
                        "RL004", path, t.lineno,
                        "rebinding `.samples` severs the live histogram "
                        "list the facades alias — mutate in place "
                        "(append/clear) or use stats.<field> = [...]",
                    ))
    return out


# -------------------------------------------------- RL005 trash-block
_RL005_SCOPE = ("src/repro/models/attention.py", "src/repro/kernels/paged_attention/")
# the ONLY functions allowed to scatter into paged pools: both route
# pad/dead-row writes to the sentinel trash block
_SCATTER_ALLOWLIST = {"paged_scatter"}
_WRITE_METHODS = {"set", "add", "multiply", "divide", "max", "min", "apply"}


def rl005(path: str, tree: ast.AST, lines: Sequence[str]) -> List[Finding]:
    if not any(path.startswith(s) or s in path for s in _RL005_SCOPE):
        return []
    out: List[Finding] = []
    for fn, _stack in enclosing_functions(tree):
        if fn.name in _SCATTER_ALLOWLIST:
            continue
        for node in ast.walk(fn):
            # <base>.at[<idx>].set(...) where base names a pool or the
            # index routes through a block table / block id
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _WRITE_METHODS):
                continue
            sub = node.func.value
            if not (isinstance(sub, ast.Subscript)
                    and isinstance(sub.value, ast.Attribute)
                    and sub.value.attr == "at"):
                continue
            base = dotted(sub.value.value)
            idx_names = {
                n.id for n in ast.walk(sub.slice) if isinstance(n, ast.Name)
            }
            pool_like = "pool" in base.rsplit(".", 1)[-1]
            table_idx = any(
                "table" in n or n == "bid" or n.endswith("_bid")
                for n in idx_names
            )
            if pool_like or table_idx:
                out.append(Finding(
                    "RL005", path, node.lineno,
                    f"paged pool write in `{fn.name}` outside the "
                    f"trash-routing helpers "
                    f"({', '.join(sorted(_SCATTER_ALLOWLIST))}) — pads/"
                    f"dead rows must land in the trash block, never a "
                    f"possibly-shared live block",
                ))
    return out


# ---------------------------------------------------- RL007 unseeded RNG
# (RL006 is the suppression-hygiene meta rule, implemented in core.py.)
# Replay determinism is a repo contract: identity gates (spec-vs-plain,
# warm-vs-cold, dynamic-vs-static) replay the SAME token streams across
# runs, and the speculative drafter must propose the same drafts every
# time. Unseeded RNG — `default_rng()` with no seed, `random.Random()`,
# or the process-global `np.random.*` / `random.*` samplers — breaks
# that silently and only on the runs you didn't save.
_RL007_GLOBAL_NP = {
    "rand", "randn", "randint", "random", "random_sample", "ranf",
    "sample", "choice", "shuffle", "permutation", "uniform", "normal",
    "standard_normal", "integers", "bytes", "beta", "binomial",
    "exponential", "gamma", "geometric", "poisson", "zipf",
}
_RL007_GLOBAL_RANDOM = {
    "random", "randint", "randrange", "getrandbits", "choice", "choices",
    "shuffle", "sample", "uniform", "triangular", "betavariate",
    "expovariate", "gammavariate", "gauss", "lognormvariate",
    "normalvariate", "vonmisesvariate", "paretovariate",
    "weibullvariate", "randbytes",
}
_RL007_SEEDED_CTORS = {
    "np.random.RandomState", "numpy.random.RandomState", "RandomState",
    "random.Random", "Random",
}


def rl007(path: str, tree: ast.AST, lines: Sequence[str]) -> List[Finding]:
    if not path.startswith(("src/", "tests/", "benchmarks/")):
        return []
    out: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        d = dotted(node.func)
        if not d:
            continue
        module, _, fn = d.rpartition(".")
        if fn == "default_rng" and not node.args and not node.keywords:
            out.append(Finding(
                "RL007", path, node.lineno,
                "default_rng() without a seed draws OS entropy — replay "
                "determinism is a repo contract (identity gates, the "
                "spec-decode drafter); pass an explicit seed",
            ))
        elif d in _RL007_SEEDED_CTORS and not node.args and not node.keywords:
            out.append(Finding(
                "RL007", path, node.lineno,
                f"{fn}() without a seed is nondeterministic across runs "
                f"— pass an explicit seed",
            ))
        elif module in ("np.random", "numpy.random") and \
                fn in _RL007_GLOBAL_NP:
            out.append(Finding(
                "RL007", path, node.lineno,
                f"process-global np.random.{fn}() depends on hidden "
                f"interpreter-wide state — use a seeded "
                f"np.random.default_rng(seed) generator",
            ))
        elif module == "random" and fn in _RL007_GLOBAL_RANDOM:
            out.append(Finding(
                "RL007", path, node.lineno,
                f"process-global random.{fn}() depends on hidden "
                f"interpreter-wide state — use a seeded random.Random("
                f"seed) (or a numpy generator)",
            ))
    return out


ALL_RULES: List[Tuple[str, str, object]] = [
    ("RL001", "recompile-hazard", rl001),
    ("RL002", "bf16-accumulation", rl002),
    ("RL003", "deprecated-surface", rl003),
    ("RL004", "stats-bypass", rl004),
    ("RL005", "trash-block-contract", rl005),
    ("RL007", "unseeded-rng", rl007),
]
