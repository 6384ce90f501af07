"""Trip-count-aware cost analysis over optimized HLO text.

Why this exists: ``compiled.cost_analysis()`` counts every ``while`` body
exactly ONCE — a federated round is scans-inside-scans (layers × local steps
× loss chunks), so XLA's number undercounts FLOPs by the product of all trip
counts (~30-100× here).  This walker parses the optimized HLO text and
propagates *multiplicity* through the call graph:

  entry ×1 → while(body/cond) × trip_count → fusion/call × 1 → …

yielding honest per-device totals:

* ``flops``     — 2·M·N·K per dot (from operand shapes + contracting dims),
                  1/elem for elementwise arithmetic, in-elems per reduce;
* ``bytes``     — fusion-boundary traffic model: every scheduled op reads its
                  operands and writes its output once (fusions are one op —
                  exactly XLA's "one HBM pass per fusion" contract);
* ``collectives`` — every all-gather/all-reduce/reduce-scatter/all-to-all/
                  collective-permute with its payload bytes, replica-group
                  size and multiplicity (ring wire cost model applied by the
                  caller in roofline.py).

Trip counts come from the loop-condition computation: the largest integer
literal compared against the induction variable (exactly how lax.scan
lowers).  Validated against XLA's own cost_analysis on unrolled modules
(tests/test_hlo_cost.py): identical dot flops; 10× on a 10-step scan.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

__all__ = ["analyze_hlo", "HloCost", "CollectiveCall"]

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"([a-z]\w*)\[([0-9,]*)\]")
_OP_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_OPCODE_RE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->")
_CONST_INT_RE = re.compile(r"\bs(?:32|64)\[\]\s+constant\((\d+)\)")

_ELEMWISE = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "abs",
    "negate", "exponential", "log", "tanh", "rsqrt", "sqrt", "power",
    "logistic", "sine", "cosine", "expm1", "log1p", "floor", "ceil",
    "round-nearest-afz", "clamp", "select", "compare", "and", "or", "xor",
    "not", "atan2", "remainder", "sign", "cbrt", "erf",
}
_NO_TRAFFIC = {
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "after-all", "opt-barrier", "partition-id", "replica-id", "iota",
}
_COLLECTIVES = {
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute", "all-gather-start", "all-reduce-start",
    "collective-permute-start",
}


def _type_bytes_elems(type_str: str) -> tuple[int, int]:
    """(bytes, elements) of a (possibly tuple) HLO type string."""
    total_b = total_e = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total_e += n
        total_b += n * _DTYPE_BYTES[dt]
    return total_b, total_e


@dataclass
class _Op:
    name: str
    opcode: str
    type_str: str
    operands: list
    line: str


@dataclass
class _Computation:
    name: str
    ops: dict = field(default_factory=dict)      # name -> _Op
    order: list = field(default_factory=list)


@dataclass
class CollectiveCall:
    kind: str
    bytes: int
    group_size: int
    multiplicity: float
    cross_pod: bool


@dataclass
class HloCost:
    flops: float = 0.0
    bytes: float = 0.0
    collectives: list = field(default_factory=list)

    def wire_bytes(self, *, pod_size: int = 0) -> tuple[float, float]:
        """(ici, dcn) per-device ring wire bytes over all collectives."""
        ici = dcn = 0.0
        for c in self.collectives:
            g = c.group_size
            if g <= 1:
                continue
            if c.kind.startswith("all-reduce"):
                wire = 2.0 * c.bytes * (g - 1) / g
            elif c.kind.startswith("collective-permute"):
                wire = float(c.bytes)
            else:
                wire = c.bytes * (g - 1) / g
            wire *= c.multiplicity
            if c.cross_pod:
                dcn += wire
            else:
                ici += wire
        return ici, dcn


def _parse_computations(text: str) -> dict:
    comps: dict[str, _Computation] = {}
    cur = None
    for raw in text.splitlines():
        line = raw.rstrip()
        if cur is None:
            m = _COMP_HDR_RE.match(line)
            if m and line.endswith("{"):
                cur = _Computation(name=m.group(1))
            continue
        if line == "}":
            comps[cur.name] = cur
            cur = None
            continue
        m = _OP_RE.match(line)
        if not m:
            continue
        name, rest = m.group(1), m.group(2)
        om = _OPCODE_RE.search(" " + rest)
        if not om:
            continue
        opcode = om.group(1)
        # om indexes into " " + rest (padded by one leading space)
        type_str = rest[: max(om.start() - 1, 0)].strip()
        paren = rest[om.end() - 1:]
        # operands: %refs inside the first balanced paren group
        depth, end = 1, 0
        for i, ch in enumerate(paren):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        operand_str = paren[:end]
        operands = _OPERAND_RE.findall(operand_str)
        cur.ops[name] = _Op(name=name, opcode=opcode, type_str=type_str,
                            operands=operands, line=line)
        cur.order.append(name)
    return comps


def _attr(line: str, key: str) -> str | None:
    m = re.search(key + r"=%?([\w.\-]+)", line)
    return m.group(1) if m else None


def _trip_count(comps: dict, cond_name: str) -> int:
    """Largest integer literal in the condition computation (scan bound)."""
    best = 1
    seen = set()

    def visit(cname):
        if cname in seen or cname not in comps:
            return
        seen.add(cname)
        nonlocal best
        for op in comps[cname].ops.values():
            for m in _CONST_INT_RE.finditer(op.line):
                best = max(best, int(m.group(1)))
            callee = _attr(op.line, "calls")
            if callee:
                visit(callee)

    visit(cond_name)
    return best


def _dot_flops(comp: _Computation, op: _Op) -> float:
    out_b, out_e = _type_bytes_elems(op.type_str)
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", op.line)
    if not m or not op.operands:
        return 2.0 * out_e            # degenerate
    cdims = [int(x) for x in m.group(1).split(",") if x]
    lhs = comp.ops.get(op.operands[0])
    if lhs is None:
        return 2.0 * out_e
    sm = _SHAPE_RE.search(lhs.type_str)
    if sm is None:
        return 2.0 * out_e
    dims = [int(x) for x in sm.group(2).split(",") if x]
    k = 1
    for c in cdims:
        if c < len(dims):
            k *= dims[c]
    return 2.0 * out_e * k


def _group_info(line: str, pod_size: int) -> tuple[int, bool]:
    m = re.search(r"replica_groups=\{\{([^}]*)\}", line)
    if m:
        ids = [int(x) for x in m.group(1).split(",") if x.strip()]
        cross = pod_size > 0 and len({i // pod_size for i in ids}) > 1
        return max(len(ids), 1), cross
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]*)\]", line)
    if m:
        gsize = int(m.group(2))
        # iota groups: contiguous stride within the device order; a group
        # crosses pods when its id span exceeds one pod's worth of ids.
        dims = [int(x) for x in m.group(3).split(",") if x]
        cross = pod_size > 0 and gsize > pod_size
        if pod_size > 0 and not cross and dims:
            # stride>1 groups (transposed iota) may still span pods
            cross = dims[0] * gsize > pod_size and dims[-1] != gsize
        return gsize, cross
    return 1, False


def analyze_hlo(text: str, *, pod_size: int = 0) -> HloCost:
    comps = _parse_computations(text)
    # entry = last computation in the module text (XLA prints ENTRY last) —
    # more robustly: the one never referenced as callee/body/cond.
    referenced = set()
    for c in comps.values():
        for op in c.ops.values():
            for key in ("calls", "body", "condition", "to_apply"):
                t = _attr(op.line, key)
                if t:
                    referenced.add(t)
    entries = [c for c in comps if c not in referenced]
    entry = entries[-1] if entries else list(comps)[-1]

    cost = HloCost()
    visiting = set()

    def walk(cname: str, mult: float, *, fused: bool):
        if cname not in comps or cname in visiting:
            return
        visiting.add(cname)
        comp = comps[cname]
        for name in comp.order:
            op = comp.ops[name]
            oc = op.opcode
            out_b, out_e = _type_bytes_elems(op.type_str)
            # --- flops ----------------------------------------------------
            if oc == "dot":
                cost.flops += mult * _dot_flops(comp, op)
            elif oc in ("reduce", "reduce-window"):
                in_b, in_e = (0, 0)
                if op.operands:
                    src = comp.ops.get(op.operands[0])
                    if src is not None:
                        in_b, in_e = _type_bytes_elems(src.type_str)
                cost.flops += mult * max(in_e, out_e)
            elif oc == "convolution":
                cost.flops += mult * 2.0 * out_e  # none emitted in this repo
            elif oc in _ELEMWISE:
                cost.flops += mult * out_e
            # --- bytes (fusion-boundary model, scheduled comps only) -------
            if not fused and oc not in _NO_TRAFFIC:
                traffic = out_b
                for operand in set(op.operands):
                    src = comp.ops.get(operand)
                    if src is not None and src.opcode != "constant":
                        ob, _ = _type_bytes_elems(src.type_str)
                        traffic += ob
                cost.bytes += mult * traffic
            # --- collectives ------------------------------------------------
            base = oc.replace("-start", "")
            if base in ("all-gather", "all-reduce", "reduce-scatter",
                        "all-to-all", "collective-permute") \
                    and not oc.endswith("-done"):
                g, cross = _group_info(op.line, pod_size)
                payload = out_b
                if base == "reduce-scatter" and op.operands:
                    src = comp.ops.get(op.operands[0])
                    if src is not None:
                        payload, _ = _type_bytes_elems(src.type_str)
                cost.collectives.append(CollectiveCall(
                    kind=base, bytes=payload, group_size=g,
                    multiplicity=mult, cross_pod=cross))
            # --- recursion ---------------------------------------------------
            if oc == "while":
                body = _attr(op.line, "body")
                cond = _attr(op.line, "condition")
                trips = _trip_count(comps, cond) if cond else 1
                if body:
                    walk(body, mult * trips, fused=False)
                if cond:
                    walk(cond, mult * (trips + 1), fused=False)
            elif oc == "fusion":
                callee = _attr(op.line, "calls")
                if callee:
                    walk(callee, mult, fused=True)
            elif oc in ("call", "async-start", "custom-call"):
                callee = _attr(op.line, "calls") or _attr(op.line, "to_apply")
                if callee:
                    walk(callee, mult, fused=fused)
            elif oc in ("reduce", "map", "sort", "scatter", "select-and-scatter"):
                pass  # to_apply bodies are per-element scalars; counted above
            elif oc == "conditional":
                for key in ("true_computation", "false_computation"):
                    t = _attr(op.line, key)
                    if t:
                        walk(t, mult, fused=fused)
        visiting.discard(cname)

    walk(entry, 1.0, fused=False)
    return cost
