"""Post-optimization HLO text parser: per-device collective wire bytes.

``compiled.as_text()`` is the only profile available on this CPU-only
container, so the roofline's collective term is derived from it.  Two
subtleties the naive "grep collective ops" approach gets wrong:

  1. Operand shapes are NOT printed in optimized HLO (operands are bare
     ``%op.name`` references) — we must read the RESULT shape of each
     collective and convert to wire bytes with the per-kind ring-algorithm
     convention (below).
  2. Collectives inside ``while`` loops (every ``lax.scan``: microbatch
     accumulation, stacked-layer stages, chunked attention) appear ONCE in
     the text but execute TRIP_COUNT times.  We reconstruct the computation
     call graph (while bodies, fusions, calls, conditionals) and multiply
     each call site's contribution by the enclosing loops' trip counts,
     which are read from the loop-condition computations' ``constant(N)``.

Wire-byte conventions (per device, ring algorithm, result bytes R, group
size G):

  all-gather          R * (G-1)/G      (R = gathered output)
  all-reduce          R * 2(G-1)/G     (reduce-scatter + all-gather phases)
  reduce-scatter      R * (G-1)        (R = scattered per-device output)
  all-to-all          R * (G-1)/G
  collective-permute  R                (point-to-point)

These are the bytes each device moves over its ICI links, i.e. the quantity
that divides by per-link bandwidth in the roofline collective term.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "u2": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

# result shape(s) then the op kind:  %x = f32[1,2]{1,0} all-reduce(
# or tuple results:  %x = (f32[..]{..}, f32[..]{..}) all-reduce(
_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_IOTA_GROUPS_RE = re.compile(r"replica_groups=\[([0-9,]+)\]<=\[\d+\]")
_EXPLICIT_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")
_CALLS_RE = re.compile(r"(?:calls|to_apply)=(%[\w\.\-]+)")
_WHILE_RE = re.compile(r"\bwhile\(.*?condition=(%[\w\.\-]+),\s*"
                       r"body=(%[\w\.\-]+)")
_BRANCH_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?(%[\w\.\-]+)\s*\(.*\)\s*->", re.M)
_CONST_RE = re.compile(r"\bconstant\((\d+)\)")


def shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _result_bytes(result: str) -> int:
    return sum(shape_bytes(d, dims) for d, dims in _SHAPE_RE.findall(result))


def group_size(line: str) -> int:
    m = _IOTA_GROUPS_RE.search(line)
    if m:
        dims = [int(x) for x in m.group(1).split(",")]
        # iota form [g0, g1, ...]: groups array shape; LAST dim = group size
        return dims[-1] if dims else 1
    m = _EXPLICIT_GROUPS_RE.search(line)
    if m:
        return len([x for x in m.group(1).split(",") if x.strip() != ""])
    return 1


def wire_bytes(kind: str, result_bytes: int, g: int) -> float:
    if g <= 1:
        return 0.0
    if kind == "all-gather":
        return result_bytes * (g - 1) / g
    if kind == "all-reduce":
        return result_bytes * 2 * (g - 1) / g
    if kind == "reduce-scatter":
        return result_bytes * (g - 1)
    if kind == "all-to-all":
        return result_bytes * (g - 1) / g
    return float(result_bytes)          # collective-permute


@dataclass
class CollSite:
    kind: str
    result_bytes: int
    group: int
    wire: float


@dataclass
class Computation:
    name: str
    collectives: List[CollSite] = field(default_factory=list)
    whiles: List[Tuple[str, str]] = field(default_factory=list)  # (cond, body)
    calls: List[str] = field(default_factory=list)
    constants: List[int] = field(default_factory=list)


def split_computations(hlo: str) -> Dict[str, Computation]:
    """Split HLO text into computations and index their contents."""
    headers = [(m.start(), m.group(1)) for m in _COMP_HDR_RE.finditer(hlo)]
    comps: Dict[str, Computation] = {}
    for i, (pos, name) in enumerate(headers):
        end = headers[i + 1][0] if i + 1 < len(headers) else len(hlo)
        body = hlo[pos:end]
        comp = Computation(name)
        for line in body.splitlines():
            cm = _COLL_RE.search(line)
            if cm and "-done(" not in line:
                shapes = _SHAPE_RE.findall(cm.group(1))
                if cm.group(3) and len(shapes) > 1:
                    # async -start: result is an (operand, result) tuple —
                    # the true result is the LAST element
                    shapes = shapes[-1:]
                rb = sum(shape_bytes(d, dims) for d, dims in shapes)
                g = group_size(line)
                comp.collectives.append(
                    CollSite(cm.group(2), rb, g, wire_bytes(cm.group(2), rb, g)))
            wm = _WHILE_RE.search(line)
            if wm:
                comp.whiles.append((wm.group(1), wm.group(2)))
                continue
            for c in _CALLS_RE.findall(line):
                comp.calls.append(c)
            bm = _BRANCH_RE.search(line)
            if bm:
                comp.calls.extend(x.strip() for x in bm.group(1).split(","))
            comp.constants.extend(int(x) for x in _CONST_RE.findall(line))
        comps[name] = comp
    return comps


def trip_count(cond: Computation) -> int:
    """Loop bound from the condition computation: the max integer constant
    (the compare bound; scans compare the induction var against len)."""
    return max(cond.constants) if cond.constants else 1


@dataclass
class CollectiveSummary:
    wire_bytes_total: float
    per_kind_wire: Dict[str, float]
    per_kind_count: Dict[str, float]     # dynamic (trip-count-weighted)
    static_sites: int

    def as_dict(self):
        return {
            "wire_bytes_per_device": self.wire_bytes_total,
            "per_kind_wire_bytes": self.per_kind_wire,
            "per_kind_dynamic_count": self.per_kind_count,
            "static_sites": self.static_sites,
        }


def collective_summary(hlo: str, entry: Optional[str] = None
                       ) -> CollectiveSummary:
    comps = split_computations(hlo)
    if entry is None:
        m = re.search(r"^ENTRY\s+(%[\w\.\-]+)", hlo, re.M)
        entry = m.group(1) if m else next(iter(comps))

    per_kind_wire = {k: 0.0 for k in COLLECTIVES}
    per_kind_count = {k: 0.0 for k in COLLECTIVES}
    static_sites = 0
    seen_sites: set = set()

    def walk(name: str, mult: float, depth: int = 0):
        nonlocal static_sites
        if depth > 64 or name not in comps:
            return
        comp = comps[name]
        for i, site in enumerate(comp.collectives):
            per_kind_wire[site.kind] += site.wire * mult
            per_kind_count[site.kind] += mult
            key = (name, i)
            if key not in seen_sites:
                seen_sites.add(key)
                static_sites += 1
        for cond, body in comp.whiles:
            tc = trip_count(comps[cond]) if cond in comps else 1
            walk(body, mult * max(tc, 1), depth + 1)
        for callee in comp.calls:
            walk(callee, mult, depth + 1)

    walk(entry, 1.0)
    return CollectiveSummary(sum(per_kind_wire.values()), per_kind_wire,
                             per_kind_count, static_sites)


# --------------------------------------------------------------------------
# remat / redundancy probes (§Perf: "count duplicate op names")
# --------------------------------------------------------------------------
def hlo_op_histogram(hlo: str) -> Dict[str, int]:
    ops = re.findall(r"=\s*(?:\([^)]*\)|\S+)\s+([a-z][\w\-]*)\(", hlo)
    hist: Dict[str, int] = {}
    for op in ops:
        hist[op] = hist.get(op, 0) + 1
    return hist


def xla_cost_dict(compiled) -> Dict[str, float]:
    """compiled.cost_analysis() as a dict ({} where the backend has none)."""
    try:
        return compiled.cost_analysis() or {}
    except Exception:
        return {}


# --------------------------------------------------------------------------
# loop-aware FLOPs / HBM-traffic model
# --------------------------------------------------------------------------
# XLA's compiled.cost_analysis() counts every while-loop body ONCE — useless
# for scan-structured programs (microbatch accumulation x stacked-layer
# stages x chunked attention = 3 nested loops). This walker rebuilds both
# totals from the optimized HLO text with per-call-site trip multipliers,
# exactly like collective_summary:
#
#   FLOPs    = sum over dot/convolution ops of 2 * |result| * |contraction|,
#              each x its enclosing loops' trip counts.
#   traffic  = per top-level op: result bytes + operand bytes (operands
#              resolved from the computation's local symbol table). Ops
#              inside FUSION bodies touch registers/VMEM, not HBM, so fusion
#              bodies are skipped for traffic (their call site's operands +
#              result already account for the HBM reads/writes); dots are
#              still harvested inside fusion bodies for FLOPs. Collectives
#              are excluded from traffic (they form the third term).

_LINE_RE = re.compile(
    r"^\s*(?:ROOT\s+)?(%[\w\.\-]+)\s*=\s*"
    r"(\([^)]*\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s+"
    r"([a-z][\w\-]*)\((.*)$")
_DIMS_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_OPERAND_RE = re.compile(r"%[\w\.\-]+")
_INDEX_RE = re.compile(r"index=(\d+)")
_WINDOW_SIZE_RE = re.compile(r"window=\{[^}]*size=([0-9x]+)")

# ops whose result/operand bytes do NOT represent fresh HBM traffic.
# "convert" is excluded because XLA:CPU's float-normalization pass wraps
# every bf16 buffer in f32 convert chains that DO NOT EXIST on the TPU
# target (native bf16) — counting them would bill phantom traffic.
_TRAFFIC_SKIP = {
    "parameter", "constant", "get-tuple-element", "bitcast", "tuple",
    "while", "conditional", "call", "fusion-start", "after-all",
    "opt-barrier", "partition-id", "replica-id", "iota-start", "convert",
} | set(COLLECTIVES) | {c + "-start" for c in COLLECTIVES} \
  | {c + "-done" for c in COLLECTIVES}


def _parse_shapes(type_str: str) -> List[Tuple[str, str]]:
    return _SHAPE_RE.findall(type_str)


def _elems(dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n


@dataclass
class CompCost:
    flops: float = 0.0           # dot/conv flops in this computation body
    traffic: float = 0.0         # top-level HBM bytes in this body


_WINDOW_OPS = ("dynamic-slice", "slice", "gather")
_PARAM_RE = re.compile(r"parameter\((\d+)\)")


def _param_billing(body_lines: List[str]
                   ) -> Tuple[Dict[int, int], Optional[int]]:
    """Per-parameter effective read bytes for a FUSION computation.

    XLA fusions read an operand fully UNLESS the fusion body only consumes
    it through (dynamic-)slice/gather windows — then HBM traffic is the
    window, not the buffer (this is what makes scan bodies cheap: the
    sliced sequence input is fused). A parameter that is the in-place
    target of a dynamic-update-slice (the scan-output accumulator pattern)
    is likewise billed at the update size, and when that DUS is the fusion
    ROOT the fusion's RESULT write is the update too (buffer aliased).

    Returns ({param_idx: window_bytes}, result_write_bytes_or_None)."""
    name_to_idx: Dict[str, int] = {}
    sym: Dict[str, List[Tuple[str, str]]] = {}
    windowed: Dict[int, int] = {}
    full: set = set()
    dus_update_bytes: Dict[str, int] = {}   # dus result name -> update size
    result_bill: Optional[int] = None
    # XLA:CPU's float-normalization wraps bf16 buffers in convert chains
    # (TPU keeps native bf16); see through convert/bitcast/copy so the
    # windowed-access analysis still recognizes the param underneath
    _ALIAS_OPS = ("convert", "bitcast", "copy", "reshape")

    def _resolve(name: str) -> str:
        seen = set()
        while name in alias and name not in seen:
            seen.add(name)
            name = alias[name]
        return name

    alias: Dict[str, str] = {}
    for line in body_lines:
        m = _LINE_RE.match(line)
        if not m:
            continue
        name, type_str, op, rest = m.groups()
        sym[name] = _parse_shapes(type_str)
        if op == "parameter":
            pm = _PARAM_RE.search(line)
            if pm:
                name_to_idx[name] = int(pm.group(1))
            continue
        depth, end = 1, len(rest)
        for i, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        operands = [_resolve(o) for o in _OPERAND_RE.findall(rest[:end])]
        if op in _ALIAS_OPS and len(operands) == 1:
            alias[name] = operands[0]
            if line.lstrip().startswith("ROOT") and \
                    operands[0] in dus_update_bytes:
                result_bill = dus_update_bytes[operands[0]]
            continue
        for k, operand in enumerate(operands):
            if operand not in name_to_idx:
                continue
            idx = name_to_idx[operand]
            if op in _WINDOW_OPS and k == 0:
                # windowed read: param is the SLICED buffer
                rb = sum(shape_bytes(d, dims)
                         for d, dims in _parse_shapes(type_str))
                windowed[idx] = windowed.get(idx, 0) + rb
            elif op == "dynamic-update-slice" and k == 0:
                # param is the in-place accumulator: read = update window
                ub = 0
                if len(operands) > 1:
                    ub = sum(shape_bytes(d, dims)
                             for d, dims in sym.get(operands[1], []))
                windowed[idx] = windowed.get(idx, 0) + ub
                dus_update_bytes[name] = ub
                if line.lstrip().startswith("ROOT"):
                    result_bill = ub
            else:
                full.add(idx)
    return ({i: b for i, b in windowed.items() if i not in full},
            result_bill)


def _analyse_computation(body_lines: List[str],
                         billing: Optional[Dict[str, Dict[int, int]]] = None
                         ) -> CompCost:
    """One pass: symbol table + dot flops + top-level traffic."""
    billing = billing or {}
    sym: Dict[str, List[Tuple[str, str]]] = {}
    cost = CompCost()
    for line in body_lines:
        m = _LINE_RE.match(line)
        if not m:
            continue
        name, type_str, op, rest = m.groups()
        shapes = _parse_shapes(type_str)
        if op == "get-tuple-element":
            src = _OPERAND_RE.search(rest)
            im = _INDEX_RE.search(line)
            if src and im and src.group(0) in sym:
                idx = int(im.group(1))
                src_shapes = sym[src.group(0)]
                if idx < len(src_shapes):
                    shapes = [src_shapes[idx]]
        sym[name] = shapes

        # ---- FLOPs --------------------------------------------------------
        if op == "dot":
            res_elems = sum(_elems(d) for _, d in shapes)
            lhs = _OPERAND_RE.search(rest)
            k = 1
            dm = _DIMS_RE.search(line)
            if lhs and dm and lhs.group(0) in sym:
                lhs_shapes = sym[lhs.group(0)]
                if lhs_shapes:
                    lhs_dims = lhs_shapes[0][1].split(",") \
                        if lhs_shapes[0][1] else []
                    for ci in dm.group(1).split(","):
                        if ci != "" and int(ci) < len(lhs_dims):
                            k *= int(lhs_dims[int(ci)])
            cost.flops += 2.0 * res_elems * k
        elif op == "convolution":
            res_elems = sum(_elems(d) for _, d in shapes)
            wm = _WINDOW_SIZE_RE.search(line)
            k = 1
            if wm:
                for s in wm.group(1).split("x"):
                    k *= int(s)
            cost.flops += 2.0 * res_elems * k

        # ---- traffic ------------------------------------------------------
        if op in _TRAFFIC_SKIP or op.endswith("-done"):
            continue
        result_bytes = sum(shape_bytes(d, dims) for d, dims in shapes)
        depth, end = 1, len(rest)
        for i, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        operand_bytes = []
        for k, operand in enumerate(_OPERAND_RE.findall(rest[:end])):
            b = sum(shape_bytes(dt, dims)
                    for dt, dims in sym.get(operand, []))
            operand_bytes.append(b)
        # windowed ops move only the WINDOW, not the backing buffer — a
        # dynamic-slice inside a T=4096 scan body must not bill the full
        # sequence array every iteration
        if op in _WINDOW_OPS:
            nbytes = 2 * result_bytes           # read window + write result
        elif op in ("dynamic-update-slice", "scatter", "select-and-scatter"):
            # in-place update: traffic = the update operand(s), not the
            # target buffer (= the largest operand) nor the aliased result
            minor = sum(operand_bytes) - (max(operand_bytes)
                                          if operand_bytes else 0)
            nbytes = 2 * minor
        elif op == "fusion":
            # operands consumed only through slices inside the fusion body
            # bill at window size (see _param_billing)
            cm = _CALLS_RE.search(line)
            pb, res_bill = billing.get(cm.group(1), ({}, None)) if cm \
                else ({}, None)
            nbytes = result_bytes if res_bill is None \
                else min(res_bill, result_bytes)
            for k, b in enumerate(operand_bytes):
                nbytes += min(pb.get(k, b), b)
        else:
            nbytes = result_bytes + sum(operand_bytes)
        cost.traffic += nbytes
    return cost


@dataclass
class CostSummary:
    flops: float
    traffic_bytes: float

    def as_dict(self):
        return {"flops_per_device": self.flops,
                "traffic_bytes_per_device": self.traffic_bytes}


def cost_summary(hlo: str, entry: Optional[str] = None) -> CostSummary:
    """Loop-aware per-device FLOPs + HBM traffic from optimized HLO text."""
    comps = split_computations(hlo)
    # re-split to get raw body lines per computation for the cost pass
    headers = [(m.start(), m.group(1)) for m in _COMP_HDR_RE.finditer(hlo)]
    bodies: Dict[str, List[str]] = {}
    for i, (pos, name) in enumerate(headers):
        end = headers[i + 1][0] if i + 1 < len(headers) else len(hlo)
        bodies[name] = hlo[pos:end].splitlines()
    billing = {name: _param_billing(lines)
               for name, lines in bodies.items()}
    costs = {name: _analyse_computation(lines, billing)
             for name, lines in bodies.items()}
    if entry is None:
        m = re.search(r"^ENTRY\s+(%[\w\.\-]+)", hlo, re.M)
        entry = m.group(1) if m else next(iter(comps))

    total = CostSummary(0.0, 0.0)

    def walk(name: str, mult: float, in_fusion: bool, depth: int = 0):
        if depth > 64 or name not in comps:
            return
        comp = comps[name]
        c = costs[name]
        total.flops += c.flops * mult
        if not in_fusion:
            total.traffic_bytes += c.traffic * mult
        for cond, body in comp.whiles:
            tc = trip_count(comps[cond]) if cond in comps else 1
            walk(body, mult * max(tc, 1), in_fusion, depth + 1)
        for callee in comp.calls:
            # fusion/reduce/map bodies: FLOPs only (VMEM-resident)
            walk(callee, mult, True, depth + 1)

    walk(entry, 1.0, False)
    return total
