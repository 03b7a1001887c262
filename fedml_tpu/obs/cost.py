"""fedcost: static per-op roofline attribution for round programs.

The flagship has sat at ~10.5% MFU across PRs 2-5 while the per-layer
explanation — CIFAR-scale convs fill at most half of the 128-wide MXU
output lanes — lived only as hand arithmetic in docs/perf.md. This module
turns that arithmetic into an instrument: every round program routed
through :func:`fedml_tpu.obs.compile.timed_build` can be lowered to HLO
and read back as a per-op table —

- conv/dot GEMM shape (M, K = kh*kw*C_in, N = C_out per feature group),
- analytic GEMM FLOPs (2*M*K*N per execution) and operand+result bytes,
- MXU output-lane fill ``min(N, 128)/128`` and reduction-lane fill
  ``min(K, 128)/128``,
- arithmetic intensity (FLOPs / bytes moved),

folded into a flop-weighted output-lane *ceiling* per program: the MFU the
program cannot exceed no matter how well XLA schedules it, because its
GEMMs leave output lanes empty. Combined with a measured duration (bench
wall clock, fedtrace compute spans) and the shared bf16 peak table this
yields achieved-FLOP/s and per-program MFU — the number the lane-packing
work on the ROADMAP is judged by.

The attribution is PURE STATIC: it only lowers (traces) the program — no
compile, no execution, no device sync — so it runs deterministically on
CPU in tier-1 and a run with attribution enabled stays bit-identical to
one without. Loop bodies are multiplied by their statically-derived trip
counts (the ``lax.scan`` counter pattern in the HLO ``while`` condition);
a loop whose trip count cannot be derived counts its body once and flags
``unknown_trip_counts`` in the summary. A loop whose condition is a
data-dependent bound AND a constant one (the packed lanes' step loop ends at
its chunk's last live step, parallel/packed.chunk_bounds) counts at the
constant: the tables of a packed program are its CEILING, the plan's whole
length, of which a round walks what ``round_counts`` reports.

This module is also the single source for FLOPs-and-peak numbers:
:data:`PEAK_BF16` / :func:`peak_flops` and :func:`fwd_flops_per_image`
moved here from bench.py so the bench, ``tools/roofline_report.py`` and
``tools/trace_report.py`` can never drift apart on ``mfu_basis``.
"""

from __future__ import annotations

import re
import threading
from typing import Optional

#: MXU systolic-array width: a GEMM contributes peak FLOPs only when both
#: the output-channel dim and the reduction dim fill this many lanes.
MXU_LANES = 128

#: bf16 peak FLOP/s keyed by the EXACT ``device_kind`` string jax reports,
#: with the source of each figure. Only kinds this repo has run on are
#: listed: a TPU that is not here is an error (:func:`peak_flops`), never a
#: neighbouring generation's number. Shared by the bench headline, the
#: roofline report and the trace analyzer so they divide by the same peak.
PEAK_BF16 = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip
    "TPU v5 lite": 197e12,
}


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s4": 1, "u4": 1,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "f32": 4, "s32": 4, "u32": 4,
    "f64": 8, "s64": 8, "u64": 8, "c64": 8, "c128": 16,
}


class UnknownDeviceKind(LookupError):
    """A TPU whose ``device_kind`` has no entry in :data:`PEAK_BF16`."""


def peak_flops(device):
    """(peak_bf16_flops, device_kind) for a jax device. ``(None, None)``
    off-TPU (a CPU run has no peak and reports no MFU); a TPU whose
    ``device_kind`` is not in :data:`PEAK_BF16` raises — a silently null or
    borrowed peak would make every MFU downstream wrong without a trace."""
    if getattr(device, "platform", None) != "tpu":
        return None, None
    kind = device.device_kind
    if kind not in PEAK_BF16:
        raise UnknownDeviceKind(
            f"no bf16 peak recorded for TPU device_kind {kind!r}; add it to "
            f"fedml_tpu.obs.cost.PEAK_BF16 with its source "
            f"(known: {sorted(PEAK_BF16)})")
    return PEAK_BF16[kind], kind


def fwd_flops_per_image(bundle, variables, input_shape, batch, dtype):
    """(forward FLOPs per image, backend) from XLA's own cost model: compile
    the eval forward on the default backend and read ``cost_analysis()``.
    Errors propagate — a backend that cannot analyse its own executable is
    a finding, not something to paper over with another backend's count."""
    import jax
    import jax.numpy as jnp

    def fwd(v, x):
        return bundle.apply_eval(v, x)

    x = jnp.zeros((batch,) + tuple(input_shape), dtype)
    ca = jax.jit(fwd).lower(variables, x).compile().cost_analysis()
    return float(ca["flops"]) / batch, jax.default_backend()


# -- HLO text parsing --------------------------------------------------------
#
# The per-op table is read from the PRE-OPTIMIZATION HLO text
# (``lowered.compiler_ir("hlo").as_hlo_text()``): shapes, dim_labels and
# group counts are all printed, and the text is available from a bare
# ``jit(...).lower(...)`` without invoking the backend compiler.

_COMP_NAME_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)")
_SHAPE_RE = re.compile(r"^([a-z0-9]+)\[([0-9,]*)\]")
_DIM_LABELS_RE = re.compile(r"dim_labels=([0-9a-z?]+)_([0-9a-z?]+)->([0-9a-z?]+)")
_ATTR_INT_RE = {
    "feature_group_count": re.compile(r"feature_group_count=(\d+)"),
    "batch_group_count": re.compile(r"batch_group_count=(\d+)"),
}
_DIMS_SET_RE = {
    "lhs_contracting": re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}"),
    "rhs_contracting": re.compile(r"rhs_contracting_dims=\{([0-9,]*)\}"),
    "lhs_batch": re.compile(r"lhs_batch_dims=\{([0-9,]*)\}"),
    "rhs_batch": re.compile(r"rhs_batch_dims=\{([0-9,]*)\}"),
}
_CALLEE_RE = {
    "to_apply": re.compile(r"to_apply=%?([\w.\-]+)"),
    "calls": re.compile(r"calls=%?([\w.\-]+)"),
    "body": re.compile(r"body=%?([\w.\-]+)"),
    "condition": re.compile(r"condition=%?([\w.\-]+)"),
}
_COMMENT_RE = re.compile(r"/\*.*?\*/")
_OPCODE_RE = re.compile(r"([\w\-]+)\((.*)$")
_GTE_INDEX_RE = re.compile(r"index=(\d+)")
_CONST_INT_RE = re.compile(r"constant\((-?\d+)\)")
_COMPARE_DIR_RE = re.compile(r"direction=(\w+)")


def _parse_shape(type_text: str):
    """'bf16[64,32,32,16]{3,2,1,0}' -> ('bf16', (64,32,32,16)); tuples and
    scalars return (dtype-or-None, dims-or-None)."""
    m = _SHAPE_RE.match(type_text.strip())
    if not m:
        return None, None
    dims = tuple(int(d) for d in m.group(2).split(",") if d) \
        if m.group(2) else ()
    return m.group(1), dims


def _operand_names(arg_text: str) -> list[str]:
    """Top-level operand names from the text following 'opcode(' (balanced
    up to the matching close paren; attrs after it are ignored)."""
    depth, out, cur = 0, [], []
    for ch in arg_text:
        if ch == "(" or ch == "{" or ch == "[":
            depth += 1
            cur.append(ch)
        elif ch == ")" and depth == 0:
            break
        elif ch == ")" or ch == "}" or ch == "]":
            depth -= 1
            cur.append(ch)
        elif ch == "," and depth == 0:
            out.append("".join(cur).strip())
            cur = []
        else:
            cur.append(ch)
    if cur:
        out.append("".join(cur).strip())
    return [o.lstrip("%") for o in out if o]


def _split_instr(line: str):
    """'name = TYPE opcode(rest...' -> (name, type_text, opcode, rest,
    is_root) or None. Tuple types (which contain parens and commas) are
    skipped over by balanced-paren scan, not regex."""
    s = line.strip()
    root = s.startswith("ROOT ")
    if root:
        s = s[5:]
    eq = s.find(" = ")
    if eq < 0:
        return None
    name = s[:eq].strip().lstrip("%")
    rhs = s[eq + 3:].lstrip()
    if rhs.startswith("("):
        depth = 0
        end = -1
        for i, ch in enumerate(rhs):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        if end < 0:
            return None
        type_text, rest = rhs[:end + 1], rhs[end + 1:].lstrip()
    else:
        sp = rhs.find(" ")
        if sp < 0:
            return None
        type_text, rest = rhs[:sp], rhs[sp + 1:].lstrip()
    m = _OPCODE_RE.match(rest)
    if not m:
        return None
    return name, type_text, m.group(1), m.group(2), root


def parse_hlo_module(text: str) -> dict:
    """Parse HLO text into {computation name: {instr name: instr dict}}.
    Each instr dict: dtype, dims, op, operands (names), attrs (raw line).
    ``/*index=N*/`` printer comments are stripped first — they otherwise
    corrupt both the type text and long operand lists."""
    comps: dict[str, dict] = {}
    entry = None
    cur: Optional[dict] = None
    for raw in text.splitlines():
        line = _COMMENT_RE.sub("", raw).rstrip()
        if cur is None:
            # computation header: a `{`-terminated line with no `=` (instr
            # lines always assign); name is the first token, `%`/signature
            # stripped. Matches both `region_0.9 {` and
            # `%fused (p: f32[2]) -> f32[2] {` printer styles.
            if line.endswith("{") and "=" not in line:
                m = _COMP_NAME_RE.match(line.strip())
                if m:
                    name = m.group(2).split("(")[0]
                    comps[name] = cur = {}
                    if m.group(1):
                        entry = name
            continue
        if line.strip() == "}":
            cur = None
            continue
        parts = _split_instr(line)
        if parts is None:
            continue
        name, type_text, op, rest, root = parts
        dtype, dims = _parse_shape(type_text)
        cur[name] = {
            "name": name, "dtype": dtype, "dims": dims, "op": op,
            "operands": _operand_names(rest), "line": line.strip(),
            "root": root,
        }
    return {"computations": comps, "entry": entry}


def _while_trip_count(instr: dict, comp: dict, comps: dict) -> Optional[int]:
    """Statically derive a while loop's trip count from the lax.scan
    counter pattern: condition ROOT ``compare(gte(i), constant(N)), LT``,
    init tuple element i a constant, body element i ``add(gte(i),
    constant(step))``. Returns None when the pattern doesn't hold."""
    cond_name = _CALLEE_RE["condition"].search(instr["line"])
    body_name = _CALLEE_RE["body"].search(instr["line"])
    if not cond_name or not body_name:
        return None
    cond = comps.get(cond_name.group(1))
    body = comps.get(body_name.group(1))
    if not cond or not body:
        return None
    root = next((i for i in cond.values() if i["root"]), None)
    if root is not None and root["op"] == "and":
        # a loop that ends at a data-dependent bound AND at a constant one
        # (parallel/packed._walk_steps) counts at the constant: its ceiling
        trips = [t for t in (_compare_trip_count(cond.get(o), cond, comp,
                                                 instr, body)
                             for o in root["operands"]) if t is not None]
        return min(trips) if trips else None
    return _compare_trip_count(root, cond, comp, instr, body)


def _compare_trip_count(root: Optional[dict], cond: dict, comp: dict,
                        instr: dict, body: dict) -> Optional[int]:
    """The trip count one ``compare`` of a while's condition implies
    (:func:`_while_trip_count`'s counter pattern), or None."""
    if root is None or root["op"] != "compare":
        return None
    mdir = _COMPARE_DIR_RE.search(root["line"])
    if not mdir or mdir.group(1) not in ("LT", "LE"):
        return None
    # which side is the counter (a gte of the loop tuple), which the bound
    idx = bound = None
    for opn in root["operands"]:
        o = cond.get(opn)
        if o is None:
            continue
        if o["op"] == "get-tuple-element":
            mi = _GTE_INDEX_RE.search(o["line"])
            idx = int(mi.group(1)) if mi else None
        elif o["op"] == "constant":
            mc = _CONST_INT_RE.search(o["line"])
            bound = int(mc.group(1)) if mc else None
    if idx is None or bound is None:
        return None
    # init value: the while operand is a tuple instruction in the caller
    init = None
    tup = comp.get(instr["operands"][0]) if instr["operands"] else None
    if tup is not None and tup["op"] == "tuple" and idx < len(tup["operands"]):
        cinit = comp.get(tup["operands"][idx])
        if cinit is not None and cinit["op"] == "constant":
            mc = _CONST_INT_RE.search(cinit["line"])
            init = int(mc.group(1)) if mc else None
    if init is None:
        return None
    # step: body ROOT tuple element idx = add(gte(idx), constant(step))
    step = None
    broot = next((i for i in body.values()
                  if i["root"] and i["op"] == "tuple"), None)
    if broot is not None and idx < len(broot["operands"]):
        add = body.get(broot["operands"][idx])
        if add is not None and add["op"] == "add":
            for opn in add["operands"]:
                o = body.get(opn)
                if o is not None and o["op"] == "constant":
                    mc = _CONST_INT_RE.search(o["line"])
                    step = int(mc.group(1)) if mc else None
    if not step or step <= 0:
        return None
    trips = bound - init
    if mdir.group(1) == "LE":
        trips += 1
    trips = -(-trips // step)
    return trips if trips >= 0 else None


def _comp_multipliers(mod: dict) -> tuple[dict, bool]:
    """Execution count per computation, ENTRY = 1, loop bodies multiplied
    by their derived trip count. Returns (multipliers, any_unknown)."""
    comps, entry = mod["computations"], mod["entry"]
    mult: dict[str, int] = {}
    unknown = [False]

    def visit(cname: str, m: int):
        if m <= 0:
            return
        mult[cname] = mult.get(cname, 0) + m
        comp = comps.get(cname, {})
        for instr in comp.values():
            op, line = instr["op"], instr["line"]
            if op == "while":
                body = _CALLEE_RE["body"].search(line)
                trips = _while_trip_count(instr, comp, comps)
                if trips is None:
                    trips = 1
                    unknown[0] = True
                if body:
                    visit(body.group(1), m * trips)
            elif op in ("call", "map", "reduce", "reduce-window", "scatter",
                        "sort", "all-reduce", "select-and-scatter"):
                cal = _CALLEE_RE["to_apply"].search(line)
                if cal:
                    visit(cal.group(1), m)
            elif op == "fusion":
                cal = _CALLEE_RE["calls"].search(line)
                if cal:
                    visit(cal.group(1), m)
            elif op == "conditional":
                # branches: count each once (upper bound is one of them)
                for b in re.findall(r"branch_computations=\{([^}]*)\}", line):
                    for cn in b.split(","):
                        visit(cn.strip().lstrip("%"), m)
                for key in ("true_computation", "false_computation"):
                    mb = re.search(key + r"=%?([\w.\-]+)", line)
                    if mb:
                        visit(mb.group(1), m)

    if entry:
        visit(entry, 1)
    return mult, unknown[0]


def _prod(dims) -> int:
    out = 1
    for d in dims:
        out *= int(d)
    return out


def _lane_fill(n: int) -> float:
    return min(int(n), MXU_LANES) / MXU_LANES


def _bytes_of(instrs: list[dict]) -> float:
    total = 0.0
    for i in instrs:
        if i is None or i.get("dims") is None:
            continue
        total += _prod(i["dims"]) * _DTYPE_BYTES.get(i.get("dtype"), 4)
    return total


def _conv_op(instr: dict, comp: dict) -> Optional[dict]:
    m = _DIM_LABELS_RE.search(instr["line"])
    if not m or instr["dims"] is None:
        return None
    _lhs_spec, ker_spec, out_spec = m.groups()
    kernel = comp.get(instr["operands"][1]) if len(instr["operands"]) > 1 \
        else None
    if kernel is None or kernel.get("dims") is None:
        return None
    kdims = kernel["dims"]
    if len(kdims) != len(ker_spec):
        return None
    k_spatial = _prod(kdims[i] for i, ch in enumerate(ker_spec)
                      if ch.isdigit())
    k_in = next((kdims[i] for i, ch in enumerate(ker_spec) if ch == "i"), 1)
    fgc = 1
    mg = _ATTR_INT_RE["feature_group_count"].search(instr["line"])
    if mg:
        fgc = int(mg.group(1))
    odims = instr["dims"]
    if len(odims) != len(out_spec):
        return None
    n_total = next((odims[i] for i, ch in enumerate(out_spec) if ch == "f"), 1)
    k = k_spatial * k_in
    n = max(1, n_total // max(1, fgc))
    m_rows = _prod(odims[i] for i, ch in enumerate(out_spec) if ch != "f")
    lhs = comp.get(instr["operands"][0]) if instr["operands"] else None
    return {
        "kind": "conv", "m": int(m_rows), "k": int(k), "n": int(n),
        "groups": int(fgc), "b": 1,
        "flops": 2.0 * _prod(odims) * k,
        "bytes": _bytes_of([lhs, kernel, instr]),
    }


def _dot_op(instr: dict, comp: dict) -> Optional[dict]:
    if len(instr["operands"]) < 2 or instr["dims"] is None:
        return None
    lhs = comp.get(instr["operands"][0])
    rhs = comp.get(instr["operands"][1])
    if lhs is None or rhs is None or lhs.get("dims") is None \
            or rhs.get("dims") is None:
        return None

    def dims_set(key):
        mm = _DIMS_SET_RE[key].search(instr["line"])
        if not mm or not mm.group(1):
            return ()
        return tuple(int(d) for d in mm.group(1).split(","))

    lc, rc = dims_set("lhs_contracting"), dims_set("rhs_contracting")
    lb, rb = dims_set("lhs_batch"), dims_set("rhs_batch")
    ldims, rdims = lhs["dims"], rhs["dims"]
    k = _prod(ldims[i] for i in lc) if lc else 1
    b = _prod(ldims[i] for i in lb) if lb else 1
    m_rows = _prod(d for i, d in enumerate(ldims) if i not in lc + lb)
    n = _prod(d for i, d in enumerate(rdims) if i not in rc + rb)
    return {
        "kind": "dot", "m": int(m_rows), "k": int(k), "n": int(n),
        "groups": 1, "b": int(b),
        "flops": 2.0 * b * m_rows * k * n,
        "bytes": _bytes_of([lhs, rhs, instr]),
    }


def op_table(hlo_text: str) -> tuple[list[dict], bool]:
    """The per-op GEMM table of an HLO module: one row per conv/dot
    instruction, with its static execution count (loop-body multiplier).
    Returns (ops, unknown_trip_counts)."""
    mod = parse_hlo_module(hlo_text)
    mult, unknown = _comp_multipliers(mod)
    ops: list[dict] = []
    for cname, comp in mod["computations"].items():
        count = mult.get(cname, 0)
        if count <= 0:
            continue
        for instr in comp.values():
            row = None
            if instr["op"] == "convolution":
                row = _conv_op(instr, comp)
            elif instr["op"] == "dot":
                row = _dot_op(instr, comp)
            if row is None:
                continue
            row.update({
                "name": instr["name"], "dtype": instr["dtype"],
                "count": int(count),
                "out_lane_fill": _lane_fill(row["n"]),
                "red_lane_fill": _lane_fill(row["k"]),
            })
            row["intensity"] = (row["flops"] / row["bytes"]
                                if row["bytes"] else 0.0)
            ops.append(row)
    return ops, unknown


def summarize(ops: list[dict], unknown_trip_counts: bool = False,
              top_k: int = 8) -> dict:
    """Fold a per-op table into the numbers a report prints: total GEMM
    FLOPs per program invocation, the flop-weighted MXU lane ceilings, a
    per-output-channel stage table (the docs/perf.md roofline rows), and
    the top-k ops by executed FLOPs."""
    total = sum(o["flops"] * o["count"] for o in ops)
    if total <= 0:
        return {"gemm_ops": 0, "gemm_flops_per_invocation": 0.0,
                "out_lane_ceiling": None, "red_lane_ceiling": None,
                "by_output_channels": {}, "top_ops": [],
                "unknown_trip_counts": unknown_trip_counts}
    out_ceiling = sum(o["flops"] * o["count"] * o["out_lane_fill"]
                      for o in ops) / total
    red_ceiling = sum(o["flops"] * o["count"] * o["red_lane_fill"]
                      for o in ops) / total
    by_n: dict[int, float] = {}
    for o in ops:
        by_n[o["n"]] = by_n.get(o["n"], 0.0) + o["flops"] * o["count"]
    stage = {
        str(n): {"out_lane_fill": _lane_fill(n),
                 "flops_frac": round(f / total, 4)}
        for n, f in sorted(by_n.items())
    }
    top = sorted(ops, key=lambda o: -o["flops"] * o["count"])[:top_k]
    return {
        "gemm_ops": len(ops),
        "gemm_flops_per_invocation": total,
        "out_lane_ceiling": round(out_ceiling, 4),
        "red_lane_ceiling": round(red_ceiling, 4),
        "by_output_channels": stage,
        "top_ops": [
            {k: (round(v, 4) if isinstance(v, float) else v)
             for k, v in o.items() if k != "intensity"}
            | {"intensity": round(o["intensity"], 2)}
            for o in top
        ],
        "unknown_trip_counts": unknown_trip_counts,
    }


def analyze_lowered(lowered, top_k: int = 8) -> dict:
    """Full static analysis of a ``jax.stages.Lowered``: the per-op table,
    its summary, and XLA's own cost-model totals (flops/bytes with loop
    bodies counted ONCE — XLA's pre-compile convention, recorded for
    comparability with ``fwd_flops_per_image``)."""
    text = lowered.compiler_ir(dialect="hlo").as_hlo_text()
    ops, unknown = op_table(text)
    rep = {"ops": ops, "summary": summarize(ops, unknown, top_k=top_k)}
    try:
        ca = lowered.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        rep["xla_cost"] = {"flops": float(ca.get("flops", 0.0)),
                           "bytes_accessed": float(ca.get("bytes accessed", 0.0))}
    except Exception:
        rep["xla_cost"] = None
    return rep


def analyze_jitted(fn, args, top_k: int = 8) -> Optional[dict]:
    """Lower a jitted callable with its call args and analyze; None when
    the callable can't be lowered (not a jit wrapper, tracing error)."""
    lower = getattr(fn, "lower", None)
    if lower is None:
        return None
    try:
        return analyze_lowered(lower(*args), top_k=top_k)
    except Exception:
        return None


def roofline(summary: dict, measured_s: float, invocations: float = 1.0,
             peak: Optional[float] = None) -> dict:
    """Achieved-FLOP/s (and MFU when a peak is known) for a program whose
    static summary and measured execution time are both in hand. The FLOP
    basis is the analytic GEMM count (multiply-accumulates only) — the
    strict roofline convention, lower than XLA's all-HLO-flops count."""
    flops = summary.get("gemm_flops_per_invocation", 0.0) * invocations
    achieved = flops / measured_s if measured_s > 0 else 0.0
    out = {
        "gemm_flops": flops,
        "achieved_gflops_per_sec": round(achieved / 1e9, 2),
        "mfu_mac": round(achieved / peak, 4) if peak else None,
        "out_lane_ceiling": summary.get("out_lane_ceiling"),
    }
    ceiling = summary.get("out_lane_ceiling")
    if peak and ceiling:
        out["mfu_vs_ceiling"] = round((achieved / peak) / ceiling, 4)
    return out


# -- runtime attribution (the timed_build hook) ------------------------------

#: mesh-path tag for programs whose rounds carry fedscope ``mesh_step`` /
#: ``mesh_round`` device spans — lets trace_report match a program's static
#: cost to its measured device time; sim-paradigm programs have no device
#: span and are matched against the round span instead.
PROGRAM_PATHS = {
    "mesh_packed_round": "packed_mesh",
}

_lock = threading.Lock()
_ENABLED = False
_TABLES: dict[str, dict] = {}   # program name -> latest attribution record


def enable_cost_attribution(on: bool) -> None:
    global _ENABLED
    _ENABLED = bool(on)


def cost_attribution_enabled() -> bool:
    return _ENABLED


_NO_ATTR = object()


def configure_from(config) -> bool:
    """Read ``config.cost_attribution``; a config without the attribute
    leaves the current setting untouched (mirrors tracer.configure_from)."""
    val = getattr(config, "cost_attribution", _NO_ATTR)
    if val is not _NO_ATTR:
        enable_cost_attribution(bool(val))
    return _ENABLED


def cost_tables() -> dict:
    """Latest attribution record per program name (copy)."""
    with _lock:
        return dict(_TABLES)


def table_for(name_prefix: str) -> Optional[dict]:
    """The attribution record for one PROGRAM by name prefix — the
    class-qualified program names ("packed_step.FedOptAPI",
    "gather_step.FedProxAPI", ...) make a process running several API
    types hold one record per program, and consumers (bench.py's adaptive
    packed arm, reports) should select the program they measured instead
    of max-by-FLOPs guessing. Longest matching name wins on ties."""
    with _lock:
        hits = [k for k in _TABLES if k.startswith(name_prefix)]
        if not hits:
            return None
        return _TABLES[max(hits, key=len)]


def reset_cost_tables() -> None:
    with _lock:
        _TABLES.clear()


def attribute_program(name: str, shape_key, fn, args) -> Optional[dict]:
    """Statically attribute one built round program: lower, tabulate,
    store under ``name``, and (when tracing) emit a ``program_cost``
    instant whose args carry the trimmed summary. Never raises — a failed
    attribution returns None and the run proceeds untouched."""
    try:
        rep = analyze_jitted(fn, args)
        if rep is None:
            return None
        record = {
            "program": name,
            "shape_key": repr(shape_key),
            "path": PROGRAM_PATHS.get(name),
            "summary": rep["summary"],
            "xla_cost": rep["xla_cost"],
            "ops": rep["ops"],
        }
        with _lock:
            _TABLES[name] = record
        from fedml_tpu.obs.tracer import tracer_if_enabled

        tr = tracer_if_enabled(0)
        if tr is not None:
            import jax

            peak, entry = peak_flops(jax.devices()[0])
            tr.instant("program_cost", cat="cost", args={
                "program": name,
                "shape_key": repr(shape_key),
                "path": record["path"],
                "summary": rep["summary"],
                "xla_cost": rep["xla_cost"],
                "peak_bf16_flops": peak,
                "peak_table_entry": entry,
            })
        return record
    except UnknownDeviceKind:
        raise
    except Exception:
        return None
