"""Trip-count-aware cost model over optimized HLO text.

``compiled.cost_analysis()`` visits while bodies once (scan bodies are NOT
multiplied by trip count), which under-counts layer-scanned models by ~L x.
This walker parses ``compiled.as_text()`` and computes:

* flops            — dot-aware (2*M*N*K), fusion-recursive, while bodies
                     multiplied by ``known_trip_count``;
* hbm_bytes        — operand+result bytes of every materialising top-level
                     op (fusion internals excluded — post-fusion HLO means
                     fusion boundaries ARE the HBM traffic);
* collective_bytes — per op kind with ring-algorithm effective-bytes
                     formulas, replica-group aware (iota + explicit formats)
                     and split ICI vs cross-pod DCN.
"""
from __future__ import annotations

import dataclasses
import math
import re
from collections import defaultdict
from typing import Dict, List, Optional

import numpy as np

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1, "token": 0,
    "opaque": 0,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
# NB: tuple types may contain "/*index=5*/" comments (with '='), so match
# balanced-paren-free tuple bodies via [^)] rather than [^=].
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*(\([^)]*\)|\S+)\s+([\w\-]+)\(")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%([\w\.\-]+)\s*\(")

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")


def _shape_info(type_str: str):
    """-> (elem_count, bytes) summed over tuple components."""
    elems = 0
    nbytes = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        elems += n
        nbytes += n * DTYPE_BYTES[dt]
    if elems == 0 and type_str.split("[")[0] in DTYPE_BYTES:
        # scalar like 'f32[]' already handled; bare 'pred' etc.
        elems, nbytes = 1, DTYPE_BYTES.get(type_str.split("[")[0], 4)
    return elems, nbytes


def _first_shape_dims(type_str: str):
    m = _SHAPE_RE.search(type_str)
    if not m or not m.group(2):
        return []
    return [int(d) for d in m.group(2).split(",")]


@dataclasses.dataclass
class Instr:
    name: str
    type_str: str
    opcode: str
    operands: List[str]
    attrs: str
    elems: int
    nbytes: int


def parse_hlo(text: str) -> Dict[str, List[Instr]]:
    comps: Dict[str, List[Instr]] = {}
    current: Optional[str] = None
    for line in text.splitlines():
        if current is None:
            m = _COMP_RE.match(line.strip())
            if m and line.rstrip().endswith("{"):
                current = m.group(1)
                comps[current] = []
            continue
        if line.strip() == "}" or line.startswith("}"):
            current = None
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, type_str, opcode = m.groups()
        rest = line[m.end():]
        depth = 1
        i = 0
        for i, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    break
        operand_str = rest[:i]
        attrs = rest[i + 1:]
        operands = re.findall(r"%([\w\.\-]+)", operand_str)
        elems, nbytes = _shape_info(type_str)
        comps[current].append(Instr(name, type_str, opcode, operands, attrs,
                                    elems, nbytes))
    return comps


# ---------------------------------------------------------------------------
# replica groups
# ---------------------------------------------------------------------------

def parse_replica_groups(attrs: str):
    """-> (group_size, groups_or_None). Handles explicit {{0,1},{2,3}} and
    iota [G,S]<=[dims]T(perm) formats."""
    m = re.search(r"replica_groups=\{\{([^}]*)\}", attrs)
    if m:
        first = m.group(1)
        size = len(first.split(","))
        groups = []
        for g in re.findall(r"\{([\d,]+)\}", attrs.split("replica_groups=")[1]):
            groups.append([int(x) for x in g.split(",")])
        return max(size, 1), groups
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?",
                  attrs)
    if m:
        G, S = int(m.group(1)), int(m.group(2))
        dims = [int(x) for x in m.group(3).split(",")]
        perm = [int(x) for x in m.group(4).split(",")] if m.group(4) else None
        arr = np.arange(int(np.prod(dims))).reshape(dims)
        if perm:
            arr = arr.transpose(perm)
        groups = arr.reshape(G, S)
        return S, groups.tolist()
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", attrs)
    if m:
        return int(m.group(2)), None
    return 1, None


def crosses_pod(groups, pod_size: int) -> bool:
    if groups is None:
        return False
    for g in groups:
        pods = {d // pod_size for d in g}
        if len(pods) > 1:
            return True
    return False


def collective_effective_bytes(opcode: str, result_bytes: int,
                               operand_bytes: int, group: int) -> float:
    """Per-device bytes crossing links (ring algorithms)."""
    if group <= 1:
        return 0.0
    if opcode.startswith("all-reduce"):
        return 2.0 * (group - 1) / group * max(result_bytes, operand_bytes)
    if opcode.startswith("all-gather"):
        return (group - 1) / group * result_bytes
    if opcode.startswith("reduce-scatter"):
        return (group - 1) / group * operand_bytes
    if opcode.startswith("all-to-all"):
        return (group - 1) / group * max(result_bytes, operand_bytes)
    if opcode.startswith("collective"):
        return float(max(result_bytes, operand_bytes))
    return 0.0


# ---------------------------------------------------------------------------
# cost walk
# ---------------------------------------------------------------------------

_SKIP_OPS = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast",
             "after-all", "partition-id", "replica-id", "iota", "domain",
             "opt-barrier"}

_FLOP_FREE = _SKIP_OPS | {"copy", "reshape", "transpose", "broadcast",
                          "slice", "dynamic-slice", "dynamic-update-slice",
                          "concatenate", "pad", "reverse", "gather",
                          "scatter", "convert", "while", "conditional",
                          "call", "fusion", "custom-call", "select",
                          "compare"}


@dataclasses.dataclass
class Cost:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    coll_ici_bytes: float = 0.0
    coll_dcn_bytes: float = 0.0
    coll_by_op: dict = dataclasses.field(default_factory=dict)
    bytes_by_op: dict = dataclasses.field(default_factory=dict)

    def __add__(self, o):
        merged = defaultdict(float)
        for d in (self.coll_by_op, o.coll_by_op):
            for k, v in d.items():
                merged[k] += v
        bmerged = defaultdict(float)
        for d in (self.bytes_by_op, o.bytes_by_op):
            for k, v in d.items():
                bmerged[k] += v
        return Cost(self.flops + o.flops, self.hbm_bytes + o.hbm_bytes,
                    self.coll_ici_bytes + o.coll_ici_bytes,
                    self.coll_dcn_bytes + o.coll_dcn_bytes, dict(merged),
                    dict(bmerged))

    def scale(self, k: float):
        return Cost(self.flops * k, self.hbm_bytes * k,
                    self.coll_ici_bytes * k, self.coll_dcn_bytes * k,
                    {kk: v * k for kk, v in self.coll_by_op.items()},
                    {kk: v * k for kk, v in self.bytes_by_op.items()})


def _fusion_io_bytes(ins: Instr, called: List[Instr], shapes) -> float:
    """HBM traffic of a fusion = true reads + true writes.

    * operands consumed only through dynamic-slice/gather inside the fusion
      count as the sliced bytes, not the whole buffer;
    * a root dynamic-update-slice writes only the update slice (the big
      buffer is aliased in place).
    """
    if not called:
        return sum(_shape_info(shapes.get(o, ""))[1] for o in ins.operands) \
            + ins.nbytes
    inner_shapes = {i.name: i.type_str for i in called}
    # param index -> inner instr
    params = {}
    for ci in called:
        if ci.opcode == "parameter":
            try:
                idx = int(ci.operands[0]) if ci.operands else int(
                    re.search(r"parameter\((\d+)\)", ci.attrs or "").group(1))
            except Exception:  # noqa: BLE001
                idx = len(params)
            params[ci.name] = idx
    # users of each inner name
    users: Dict[str, list] = defaultdict(list)
    for ci in called:
        for o in ci.operands:
            users[o].append(ci)
    # several inner parameters may bind the same outer buffer: count each
    # unique outer operand once (at its widest access)
    per_outer: Dict[str, float] = {}
    for pname, idx in params.items():
        if idx >= len(ins.operands):
            continue
        outer = ins.operands[idx]
        full = _shape_info(shapes.get(outer, ""))[1]
        us = users.get(pname, [])
        if us and all(u.opcode in ("dynamic-slice", "gather") for u in us):
            eff = float(sum(u.nbytes for u in us))
        elif us and all(u.opcode == "dynamic-update-slice" and
                        u.operands and u.operands[0] == pname for u in us):
            eff = 0.0  # pure in-place write target
        else:
            eff = float(full)
        per_outer[outer] = max(per_outer.get(outer, 0.0), eff)
    read = sum(per_outer.values())
    root = called[-1]
    if root.opcode == "dynamic-update-slice" and len(root.operands) > 1:
        write = 2.0 * _shape_info(
            inner_shapes.get(root.operands[1], ""))[1]
    else:
        write = ins.nbytes
    return read + write


def _trip_count(instr: Instr, comps, symtab) -> float:
    m = re.search(r'known_trip_count[\'"]?:\s*\{[\'"]?n[\'"]?:\s*[\'"]?(\d+)',
                  instr.attrs)
    if m:
        return float(m.group(1))
    m = re.search(r"condition=%?([\w\.\-]+)", instr.attrs)
    if m and m.group(1) in comps:
        for ci in comps[m.group(1)]:
            if ci.opcode == "constant":
                mm = re.search(r"constant\((\d+)\)", ci.attrs) or \
                    re.search(r"\((\d+)\)", ci.type_str)
                if mm:
                    return float(mm.group(1))
        for ci in comps[m.group(1)]:
            mm = re.search(r"constant\((\d+)\)",
                           ci.name + ci.attrs)
            if mm:
                return float(mm.group(1))
    return 1.0


def _dot_flops(instr: Instr, shapes: Dict[str, str]) -> float:
    out_elems = instr.elems
    lhs_t = shapes.get(instr.operands[0], "")
    dims = _first_shape_dims(lhs_t)
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", instr.attrs)
    k = 1
    if m and m.group(1) and dims:
        for d in m.group(1).split(","):
            di = int(d)
            if di < len(dims):
                k *= dims[di]
    return 2.0 * out_elems * k


def _conv_flops(instr: Instr, shapes: Dict[str, str]) -> float:
    rhs_t = shapes.get(instr.operands[1], "") if len(instr.operands) > 1 else ""
    kdims = _first_shape_dims(rhs_t)
    out_elems = instr.elems
    if not kdims:
        return 2.0 * out_elems
    # HWIO kernel: flops = 2 * out * (kh*kw*cin)
    per_out = 2.0 * float(np.prod(kdims[:-1]))
    return per_out * out_elems


def computation_cost(name: str, comps, pod_size: int,
                     _memo=None) -> Cost:
    if _memo is None:
        _memo = {}
    if name in _memo:
        return _memo[name]
    _memo[name] = Cost()  # cycle guard
    instrs = comps.get(name, [])
    shapes = {i.name: i.type_str for i in instrs}
    total = Cost()
    for ins in instrs:
        op = ins.opcode
        c = Cost()
        operand_bytes = sum(
            _shape_info(shapes.get(o, ""))[1] for o in ins.operands)
        if op == "dot":
            c.flops = _dot_flops(ins, shapes)
            c.hbm_bytes = operand_bytes + ins.nbytes
        elif op == "convolution":
            c.flops = _conv_flops(ins, shapes)
            c.hbm_bytes = operand_bytes + ins.nbytes
        elif op.startswith(COLLECTIVES) and not op.endswith("-done"):
            group, groups = parse_replica_groups(ins.attrs)
            eff = collective_effective_bytes(op, ins.nbytes, operand_bytes,
                                             group)
            base = op.replace("-start", "")
            c.coll_by_op = {base: eff}
            if pod_size and crosses_pod(groups, pod_size):
                c.coll_dcn_bytes = eff
            else:
                c.coll_ici_bytes = eff
            c.hbm_bytes = operand_bytes + ins.nbytes
        elif op == "fusion":
            m = re.search(r"calls=%?([\w\.\-]+)", ins.attrs)
            if m:
                inner = computation_cost(m.group(1), comps, pod_size, _memo)
                c = c + Cost(flops=inner.flops)
                c.coll_ici_bytes += inner.coll_ici_bytes
                c.coll_dcn_bytes += inner.coll_dcn_bytes
                c.hbm_bytes += _fusion_io_bytes(ins, comps.get(m.group(1), []),
                                                shapes)
            else:
                c.hbm_bytes += operand_bytes + ins.nbytes
        elif op in ("call", "conditional", "async-start", "custom-call"):
            for cname in re.findall(
                    r"(?:calls|to_apply|body|branch_computations)=\{?%?([\w\.\-]+)",
                    ins.attrs):
                c = c + computation_cost(cname, comps, pod_size, _memo)
            c.hbm_bytes += operand_bytes + ins.nbytes
        elif op == "while":
            mb = re.search(r"body=%?([\w\.\-]+)", ins.attrs)
            trips = _trip_count(ins, comps, shapes)
            if mb:
                body = computation_cost(mb.group(1), comps, pod_size, _memo)
                c = c + body.scale(trips)
        elif op in _SKIP_OPS:
            pass
        elif op == "dynamic-update-slice":
            # in-place semantics: traffic = read+write of the update slice
            upd = _shape_info(shapes.get(ins.operands[1], ""))[1] \
                if len(ins.operands) > 1 else ins.nbytes
            c.hbm_bytes = 2.0 * upd
        elif op in ("dynamic-slice", "gather"):
            c.hbm_bytes = 2.0 * ins.nbytes  # read slice + write result
        elif op == "scatter":
            upd = _shape_info(shapes.get(ins.operands[2], ""))[1] \
                if len(ins.operands) > 2 else ins.nbytes
            c.hbm_bytes = 3.0 * upd
        else:
            # elementwise / reduce / copy etc: 1 flop per output elem
            if op not in _FLOP_FREE:
                c.flops = float(ins.elems)
            if op not in ("reshape", "broadcast", "convert"):
                c.hbm_bytes = operand_bytes + ins.nbytes
        if c.hbm_bytes and not c.bytes_by_op:
            c.bytes_by_op = {op: c.hbm_bytes}
        total = total + c
    _memo[name] = total
    return total


def machine_balance(device_kind: str) -> float:
    """Peak FLOP/s over HBM bytes/s of ``device_kind``, in flops per byte.
    A computation whose arithmetic intensity sits below it is
    bandwidth-bound: more compute cannot speed it up, only fewer bytes
    can."""
    from repro.roofline.analysis import peaks_for
    p = peaks_for(device_kind)
    return p.flops / p.hbm_bw


def arithmetic_intensity(cost: Cost) -> float:
    """flops per HBM byte of a walked computation (inf when byte-free)."""
    if cost.hbm_bytes <= 0:
        return float("inf")
    return cost.flops / cost.hbm_bytes


def is_bandwidth_bound(cost: Cost, *, device_kind: str) -> bool:
    """True when the computation's intensity sits below the machine
    balance of ``device_kind``. The batched-codec CI assertion: the fused
    quantize stage must stay bandwidth-bound (it streams rows; if
    intensity ever climbs the fusion regressed into recomputation)."""
    return arithmetic_intensity(cost) < machine_balance(device_kind)


def entry_cost(text: str, pod_size: int = 0) -> Cost:
    comps = parse_hlo(text)
    entry = None
    for line in text.splitlines():
        if line.startswith("ENTRY"):
            m = re.match(r"ENTRY\s+%?([\w\.\-]+)", line)
            if m:
                entry = m.group(1)
            break
    if entry is None:
        # fall back: computation with most instructions
        entry = max(comps, key=lambda k: len(comps[k]))
    return computation_cost(entry, comps, pod_size)
