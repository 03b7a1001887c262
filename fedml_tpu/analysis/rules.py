"""The fedlint rule catalog.

Each rule is ``check(pkg: PackageIndex, graph: TracedGraph) -> [Finding]``.
Rule IDs, docs and examples: docs/DESIGN.md "Static analysis (fedlint)".
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from fedml_tpu.analysis.callgraph import TracedGraph
from fedml_tpu.analysis.findings import Finding
from fedml_tpu.analysis.index import (
    ModuleInfo,
    PackageIndex,
    dotted_name,
    resolve_dotted_head,
    walk_excluding_nested,
)

# --------------------------------------------------------- traced-purity

#: exact impure callables (after import-alias resolution)
_CLOCK_CALLS = {
    "time.time", "time.time_ns", "time.perf_counter", "time.perf_counter_ns",
    "time.monotonic", "time.monotonic_ns", "time.process_time",
    "datetime.datetime.now", "datetime.datetime.utcnow",
}
#: impure module prefixes: any call below these is OS entropy / host RNG
_RNG_PREFIXES = ("numpy.random.", "random.")
#: impure bare builtins (``jax.debug.print`` is fine — it is an attribute)
_IO_BUILTINS = {"print", "open", "input"}


def check_traced_purity(pkg: PackageIndex, graph: TracedGraph) -> List[Finding]:
    out: List[Finding] = []
    for fn in sorted(
        graph.reachable, key=lambda f: (f.module.relpath, f.node.lineno)
    ):
        mod = fn.module
        root = graph.root_of.get(fn, fn.qualname)
        via = "" if root == fn.qualname else f" (reached from traced root '{root}')"

        def emit(lineno: int, what: str):
            out.append(Finding(
                "traced-purity", mod.relpath, lineno,
                f"{what} inside traced function '{fn.qualname}'{via}",
            ))

        for node in walk_excluding_nested(fn.node):
            if isinstance(node, ast.Call):
                d = dotted_name(node.func)
                if d is None:
                    continue
                real = resolve_dotted_head(mod, d)
                if real in _CLOCK_CALLS:
                    emit(node.lineno, f"wall-clock read '{d}()'")
                elif any(
                    real.startswith(p) or real == p[:-1]
                    for p in _RNG_PREFIXES
                ):
                    emit(node.lineno,
                         f"host RNG call '{d}()' (thread a jax PRNG key in)")
                elif real in _IO_BUILTINS:
                    emit(node.lineno,
                         f"host I/O call '{d}()' (use jax.debug.print / "
                         "jax.debug.callback for traced values)")
            elif isinstance(node, (ast.Global, ast.Nonlocal)):
                kind = "global" if isinstance(node, ast.Global) else "nonlocal"
                emit(node.lineno,
                     f"'{kind} {', '.join(node.names)}' rebinding "
                     "(trace-time side effect; thread state through "
                     "carry/returns)")
            elif isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = (
                    node.targets if isinstance(node, ast.Assign)
                    else [node.target]
                )
                for t in targets:
                    if isinstance(t, ast.Attribute) and isinstance(
                        t.value, ast.Name
                    ) and t.value.id == "self":
                        emit(node.lineno,
                             f"mutation of 'self.{t.attr}' (runs once at "
                             "trace time, not per call)")
    return out


# -------------------------------------------------------- retrace-hazard

def _is_static_only_param(arg: ast.arg, default: Optional[ast.AST]) -> Optional[str]:
    """'str' if this parameter is host-typed and cannot trace.

    Only str is flagged: a str arg to an un-static jit fails (or retraces)
    per distinct value, while dict/list params are routinely pytrees of
    arrays and trace fine.
    """
    if default is not None and isinstance(default, ast.Constant) \
            and isinstance(default.value, str):
        return "str"
    ann = arg.annotation
    if isinstance(ann, ast.Name) and ann.id == "str":
        return "str"
    if isinstance(ann, ast.Constant) and ann.value == "str":
        return "str"
    return None


def check_retrace_hazard(pkg: PackageIndex, graph: TracedGraph) -> List[Finding]:
    out: List[Finding] = []
    # (a) host-typed params entering jit/pjit without static_arg* declarations
    for fn, root in sorted(
        graph.roots.items(),
        key=lambda kv: (kv[0].module.relpath, kv[0].node.lineno),
    ):
        if root.kind not in ("jit", "pjit") or root.has_static_args:
            continue
        if isinstance(fn.node, ast.Lambda):
            continue
        a = fn.node.args
        pos = a.posonlyargs + a.args
        defaults: List[Optional[ast.AST]] = (
            [None] * (len(pos) - len(a.defaults)) + list(a.defaults)
        )
        params = list(zip(pos, defaults)) + list(
            zip(a.kwonlyargs, a.kw_defaults))
        for arg, default in params:
            if arg.arg in ("self", "cls"):
                continue
            kind = _is_static_only_param(arg, default)
            if kind:
                # anchor at the def, not the jit call: the call may live in
                # another module, and suppressions key on (path, line)
                out.append(Finding(
                    "retrace-hazard", fn.module.relpath, fn.node.lineno,
                    f"{kind} parameter '{arg.arg}' of '{fn.qualname}' "
                    f"enters {root.kind} without static_argnums/"
                    "static_argnames (host types retrace or fail per value)",
                ))
    # (b) f-strings built inside traced bodies. Raise/assert subtrees are
    # exempt: an f-string in a raise is trace-time shape validation that
    # only ever formats when tracing already failed.
    for fn in sorted(
        graph.reachable, key=lambda f: (f.module.relpath, f.node.lineno)
    ):
        for node in _walk_skipping_raises(fn.node):
            if isinstance(node, ast.JoinedStr) and node.values and any(
                isinstance(v, ast.FormattedValue) for v in node.values
            ):
                out.append(Finding(
                    "retrace-hazard", fn.module.relpath, node.lineno,
                    f"f-string constructed inside traced function "
                    f"'{fn.qualname}' (formats trace-time reprs, and a "
                    "tracer in the template retraces per value)",
                ))
    return out


def _walk_skipping_raises(func_node):
    from fedml_tpu.analysis.index import ScopeNode

    stack = list(ast.iter_child_nodes(func_node))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.Raise, ast.Assert)) \
                or isinstance(node, ScopeNode):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


# ------------------------------------------------------------ seeded-rng

def check_seeded_rng(pkg: PackageIndex, graph: TracedGraph) -> List[Finding]:
    out: List[Finding] = []
    for mod in pkg.modules:
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            d = dotted_name(node.func)
            if d is None:
                continue
            real = resolve_dotted_head(mod, d)
            if real.endswith("numpy.random.default_rng") \
                    or real == "numpy.random.default_rng":
                if not node.args and not node.keywords:
                    out.append(Finding(
                        "seeded-rng", mod.relpath, node.lineno,
                        f"'{d}()' without a seed draws OS entropy — every "
                        "generator must derive from an explicit seed "
                        "expression for run determinism",
                    ))
    return out


# ------------------------------------------- protocol-exhaustiveness

_REGISTER = "register_message_receive_handler"


def _resolve_msg_name(
    pkg: PackageIndex, mod: ModuleInfo, name: str
) -> Optional[Tuple[str, str]]:
    """(defining modname, constant name) for a MSG_TYPE reference."""
    if name in mod.msg_constants:
        return (mod.modname, name)
    target = mod.imports.get(name)
    if target is not None:
        tmod = pkg.by_modname.get(target[0])
        if tmod is not None and target[1] in tmod.msg_constants:
            return (tmod.modname, target[1])
    return None


def check_protocol_exhaustiveness(
    pkg: PackageIndex, graph: TracedGraph
) -> List[Finding]:
    out: List[Finding] = []
    defined: Dict[Tuple[str, str], Tuple[ModuleInfo, int]] = {}
    send_only: Set[Tuple[str, str]] = set()
    for mod in pkg.modules:
        for name, lineno in mod.msg_constants.items():
            defined[(mod.modname, name)] = (mod, lineno)
        for name in mod.send_only:
            key = _resolve_msg_name(pkg, mod, name)
            if key is not None:
                send_only.add(key)
    handled: Set[Tuple[str, str]] = set()
    for mod in pkg.modules:
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            d = dotted_name(node.func)
            if d is None or d.split(".")[-1] != _REGISTER or not node.args:
                continue
            arg = node.args[0]
            if isinstance(arg, ast.Name):
                key = _resolve_msg_name(pkg, mod, arg.id)
                if key is None:
                    out.append(Finding(
                        "protocol-exhaustiveness", mod.relpath, node.lineno,
                        f"handler registered for '{arg.id}', which is not a "
                        "defined MSG_TYPE_* constant in this package",
                    ))
                else:
                    handled.add(key)
            elif isinstance(arg, ast.Constant):
                out.append(Finding(
                    "protocol-exhaustiveness", mod.relpath, node.lineno,
                    f"handler registered for literal {arg.value!r}; register "
                    "the named MSG_TYPE_* constant so exhaustiveness is "
                    "checkable",
                ))
            # attributes / computed types: out of scope, skipped
    for key, (mod, lineno) in sorted(
        defined.items(), key=lambda kv: (kv[1][0].relpath, kv[1][1])
    ):
        if key in handled or key in send_only:
            continue
        out.append(Finding(
            "protocol-exhaustiveness", mod.relpath, lineno,
            f"'{key[1]}' has no registered receive handler anywhere in the "
            "package; register one or list it in SEND_ONLY_MSG_TYPES",
        ))
    return out


# ------------------------------------------------------ config-flag-drift

#: receivers whose attribute reads are treated as config-surface reads
_CONFIG_RECEIVERS = {"config", "cfg", "args"}


def _flag_definitions(pkg: PackageIndex) -> Dict[ModuleInfo, List[Tuple[str, int]]]:
    """module -> [(flag name, add_argument lineno), ...] for every module
    that defines CLI flags (the ONE place the add_argument shape is matched,
    so flag-module detection and flag collection cannot disagree)."""
    defs: Dict[ModuleInfo, List[Tuple[str, int]]] = {}
    for mod in pkg.modules:
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call) and isinstance(
                node.func, ast.Attribute
            ) and node.func.attr == "add_argument" and node.args \
                    and isinstance(node.args[0], ast.Constant) \
                    and isinstance(node.args[0].value, str) \
                    and node.args[0].value.startswith("--"):
                name = node.args[0].value.lstrip("-").replace("-", "_")
                defs.setdefault(mod, []).append((name, node.lineno))
    return defs


def check_config_flag_drift(
    pkg: PackageIndex, graph: TracedGraph
) -> List[Finding]:
    out: List[Finding] = []
    flag_defs = _flag_definitions(pkg)
    if not flag_defs:
        return out
    flag_mod_names = {m.modname for m in flag_defs}
    flags: Dict[str, Tuple[ModuleInfo, int]] = {}
    defined_attrs: Set[str] = {"config_yaml"}
    for mod, pairs in flag_defs.items():
        for name, lineno in pairs:
            flags.setdefault(name, (mod, lineno))
            defined_attrs.add(name)
        # dataclass fields + methods of the config classes widen the legal
        # attribute surface (fields without a CLI flag are still readable)
        for cls_node in mod.tree.body:
            if not isinstance(cls_node, ast.ClassDef):
                continue
            for stmt in cls_node.body:
                if isinstance(stmt, ast.AnnAssign) and isinstance(
                    stmt.target, ast.Name
                ):
                    defined_attrs.add(stmt.target.id)
                elif isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    defined_attrs.add(stmt.name)

    # Reads that mark a flag as used, broad on purpose — a flag consumed
    # through ANY spelling counts:
    #  - attribute read of the name anywhere, EXCEPT the ``defaults.x``
    #    argparse-bridge idiom inside a flag-defining module (add_args
    #    reads every default, which would mark everything used),
    #  - a string constant equal to the flag name anywhere (the
    #    ``getattr(cfg, "flag", ...)`` / field-name-tuple idioms).
    reads: Set[str] = set()
    config_reads: List[Tuple[ModuleInfo, int, str]] = []
    for mod in pkg.modules:
        in_flag_mod = mod.modname in flag_mod_names
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Constant) and isinstance(node.value, str):
                reads.add(node.value)
                continue
            if not (isinstance(node, ast.Attribute)
                    and isinstance(node.ctx, ast.Load)):
                continue
            recv = None
            if isinstance(node.value, ast.Name):
                recv = node.value.id
            elif isinstance(node.value, ast.Attribute) and isinstance(
                node.value.value, ast.Name
            ) and node.value.value.id == "self":
                recv = node.value.attr
            if not (in_flag_mod and recv == "defaults"):
                reads.add(node.attr)
            if recv in _CONFIG_RECEIVERS:
                config_reads.append((mod, node.lineno, node.attr))

    for name, (mod, lineno) in sorted(
        flags.items(), key=lambda kv: (kv[1][0].relpath, kv[1][1])
    ):
        if name not in reads:
            out.append(Finding(
                "config-flag-drift", mod.relpath, lineno,
                f"flag '--{name}' is defined but never read anywhere in "
                "the package — dead flag (remove it or wire it up)",
            ))
    for mod, lineno, attr in config_reads:
        if attr.startswith("__") or attr in defined_attrs:
            continue
        out.append(Finding(
            "config-flag-drift", mod.relpath, lineno,
            f"read of config attribute '.{attr}' which no flag or config "
            "field defines — likely a misspelled or removed flag",
        ))
    return out


# -------------------------------------------------------- trace-coverage

#: the round entry point the fedtrace wrapper owns (fedavg.py run_round
#: wraps _run_round_inner)
_TRACED_ENTRY_POINTS = {"run_round"}
#: calls that prove a method opens the trace gate itself (the head-sampled
#: gate counts: sampling is the gate's fedsketch form, not a bypass)
_TRACE_GATES = {"tracer_if_enabled", "tracer_if_sampled", "get_tracer"}
#: span-opening attribute calls on a tracer
_SPAN_OPENERS = {"span", "begin_span", "emit_complete"}


def _is_super_delegation(node: ast.Call) -> bool:
    """``super().run_round(...)`` — the override funnels back into the
    traced base wrapper."""
    f = node.func
    return (isinstance(f, ast.Attribute) and f.attr in _TRACED_ENTRY_POINTS
            and isinstance(f.value, ast.Call)
            and isinstance(f.value.func, ast.Name)
            and f.value.func.id == "super")


def check_trace_coverage(pkg: PackageIndex, graph: TracedGraph) -> List[Finding]:
    """Every ``run_round`` method must route through the traced span wrapper (fedml_tpu/obs): fedtrace's one-timeline guarantee
    holds only because the base ``run_round`` is THE wrapper and paradigm
    logic lives in ``_run_round_inner``. An override of the entry point that
    neither opens a span itself nor delegates to ``super()`` silently drops
    its paradigm's rounds from the trace — exactly the mesh gap this rule
    was added to close (ISSUE 5)."""
    out: List[Finding] = []
    for mod in pkg.modules:
        for fn in mod.functions:
            if fn.name not in _TRACED_ENTRY_POINTS or fn.cls is None:
                continue
            if isinstance(fn.node, ast.Lambda):
                continue
            opens_gate = opens_span = delegates = False
            for node in walk_excluding_nested(fn.node):
                if not isinstance(node, ast.Call):
                    continue
                if _is_super_delegation(node):
                    delegates = True
                    break
                d = dotted_name(node.func)
                if d is None:
                    continue
                tail = d.split(".")[-1]
                if tail in _TRACE_GATES:
                    opens_gate = True
                elif tail in _SPAN_OPENERS:
                    opens_span = True
            if delegates or (opens_gate and opens_span):
                continue
            out.append(Finding(
                "trace-coverage", mod.relpath, fn.node.lineno,
                f"'{fn.qualname}' overrides traced entry point '{fn.name}' "
                "without routing through the span wrapper — rename it to "
                "'_run_round_inner' (the base run_round wraps that), "
                "delegate via super(), or open the round span itself",
            ))
    return out


def check_unguarded_shared_write(
    pkg: PackageIndex, graph: TracedGraph
) -> List[Finding]:
    """fedrace (ISSUE 17): writes to thread-shared fields outside the lock
    that guards the majority of their accesses. The whole model — thread
    roots, guarded-by inference, the __init__ single-writer carve-out —
    lives in analysis/threads.py and is built once per package."""
    from fedml_tpu.analysis import threads
    return threads.model_for(pkg).findings("unguarded-shared-write")


def check_check_then_act(
    pkg: PackageIndex, graph: TracedGraph
) -> List[Finding]:
    """fedrace (ISSUE 17): reads of a guarded shared field outside its
    guard — the value checked can change before the acting write runs."""
    from fedml_tpu.analysis import threads
    return threads.model_for(pkg).findings("check-then-act")


def check_blocking_under_lock(
    pkg: PackageIndex, graph: TracedGraph
) -> List[Finding]:
    """fedrace (ISSUE 17): sleep/join/put/send_message or second-lock
    acquisition while holding a lock — every contender stalls with it."""
    from fedml_tpu.analysis import threads
    return threads.model_for(pkg).findings("blocking-under-lock")


#: checkable rule-id -> implementation (bad-suppression is emitted by the
#: suppression parser, not a checker)
CHECKS = {
    "traced-purity": check_traced_purity,
    "retrace-hazard": check_retrace_hazard,
    "seeded-rng": check_seeded_rng,
    "protocol-exhaustiveness": check_protocol_exhaustiveness,
    "config-flag-drift": check_config_flag_drift,
    "trace-coverage": check_trace_coverage,
    "unguarded-shared-write": check_unguarded_shared_write,
    "check-then-act": check_check_then_act,
    "blocking-under-lock": check_blocking_under_lock,
}
