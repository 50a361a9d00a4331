"""Trip-count-aware cost analysis over optimized HLO text.

XLA's ``compiled.cost_analysis()`` counts each while-loop body ONCE, which
silently undercounts scan-over-layers models by ~n_layers×.  This module
re-derives FLOPs / HBM-bytes / collective-bytes by walking the computation
call graph with ``known_trip_count`` multipliers from the HLO backend_config:

* FLOPs: dots contribute 2·|result|·K (K = contracted extent of the lhs),
  elementwise arithmetic contributes |result|;
* bytes: per top-level op, operand + result buffer sizes (the same HBM-traffic
  model XLA's own metric uses — fusion internals are free, fusion boundaries
  materialize);
* collectives: result sizes of all-reduce / all-gather / reduce-scatter /
  all-to-all / collective-permute, per kind.

Everything multiplies through while-loop trip counts, so a 126-layer scanned
model reports 126× its layer body — verified against unrolled references in
tests/test_launch.py.
"""
from __future__ import annotations

import dataclasses
import math
import re

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "token": 0, "opaque": 0,
}

_TYPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.+?)\s+([\w\-]+)\((.*?)\)(.*)$")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"')

_ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "power",
    "exponential", "log", "tanh", "rsqrt", "sqrt", "negate", "abs", "floor",
    "remainder", "and", "or", "xor", "shift-left", "shift-right-logical",
    "shift-right-arithmetic", "compare", "select", "convert", "cosine", "sine",
    "logistic", "exponential-minus-one", "clamp", "round-nearest-even",
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shapes_of(type_str: str):
    """All (dtype, [dims]) in a (possibly tuple) HLO type string."""
    return [(dt, [int(x) for x in dims.split(",")] if dims else [])
            for dt, dims in _TYPE_RE.findall(type_str)]


def _bytes_of(type_str: str) -> int:
    return sum(_DTYPE_BYTES.get(dt, 4) * math.prod(d)
               for dt, d in _shapes_of(type_str))


def _elems_of(type_str: str) -> int:
    return sum(math.prod(d) for _, d in _shapes_of(type_str))


@dataclasses.dataclass
class Op:
    name: str
    type_str: str
    opcode: str
    operands: list
    rest: str


def parse_computations(hlo: str) -> dict:
    comps: dict[str, list[Op]] = {}
    cur_name, cur_ops = None, []
    for line in hlo.splitlines():
        s = line.rstrip()
        header = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$", s)
        if header and " = " not in s:
            cur_name, cur_ops = header.group(1), []
            comps[cur_name] = cur_ops
            continue
        if s.startswith("}"):
            cur_name = None
            continue
        if cur_name is None:
            continue
        m = _OP_RE.match(s)
        if not m:
            continue
        name, type_str, opcode, args, rest = m.groups()
        operands = re.findall(r"%([\w.\-]+)", args)
        cur_ops.append(Op(name, type_str, opcode, operands, rest))
    return comps


def _called(rest: str, key: str):
    m = re.search(key + r"=%?([\w.\-]+)", rest)
    return m.group(1) if m else None


class HloCost:
    def __init__(self, hlo_text: str):
        self.comps = parse_computations(hlo_text)
        self.types: dict[str, dict[str, str]] = {
            cname: {op.name: op.type_str for op in ops}
            for cname, ops in self.comps.items()
        }
        self._memo: dict[str, tuple] = {}
        entry = re.search(r"^ENTRY\s+%?([\w.\-]+)", hlo_text, re.M)
        self.entry = entry.group(1) if entry else next(iter(self.comps))

    def _dot_flops(self, cname: str, op: Op) -> float:
        out_elems = _elems_of(op.type_str)
        lhs = op.operands[0] if op.operands else None
        lhs_type = self.types[cname].get(lhs, "")
        shapes = _shapes_of(lhs_type)
        if not shapes:
            return 0.0
        dims = shapes[0][1]
        m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", op.rest)
        k = 1
        if m and m.group(1):
            for idx in m.group(1).split(","):
                i = int(idx)
                if i < len(dims):
                    k *= dims[i]
        return 2.0 * out_elems * k

    def analyze(self, cname: str | None = None) -> dict:
        cname = cname or self.entry
        if cname in self._memo:
            return self._memo[cname]
        flops = bytes_ = 0.0
        coll = {k: 0.0 for k in _COLLECTIVES}
        for op in self.comps.get(cname, []):
            oc = op.opcode
            if oc in ("parameter", "constant", "get-tuple-element", "tuple",
                      "bitcast", "after-all", "partition-id"):
                continue
            # bytes: operands + result (fusion internals never reach here
            # because we only recurse for control flow, not fusion bodies).
            # dynamic-(update-)slice is in-place on the big buffer: only the
            # slice region moves (XLA aliases the operand), so counting the
            # full operand would bill a loop-carried KV cache per iteration.
            if oc == "dynamic-slice":
                op_bytes = 2 * _bytes_of(op.type_str)
            elif oc == "dynamic-update-slice":
                upd = (self.types[cname].get(op.operands[1], "")
                       if len(op.operands) > 1 else "")
                op_bytes = 2 * _bytes_of(upd)
            else:
                op_bytes = _bytes_of(op.type_str)
                for o in op.operands:
                    t = self.types[cname].get(o)
                    if t:
                        op_bytes += _bytes_of(t)
            mult = 1.0
            sub = None
            if oc == "while":
                body = _called(op.rest, "body")
                tm = _TRIP_RE.search(op.rest)
                mult = float(tm.group(1)) if tm else 1.0
                sub = body
                op_bytes = 0  # the loop op itself moves no data; body does
            elif oc == "fusion":
                sub_name = _called(op.rest, "calls")
                s = self.analyze(sub_name) if sub_name else {"flops": 0}
                flops += s["flops"]          # fused compute still executes
                for k in _COLLECTIVES:
                    coll[k] += s.get(k, 0.0)
            elif oc in ("call", "custom-call"):
                sub = _called(op.rest, "to_apply")
            elif oc == "conditional":
                branches = re.findall(r"branch_computations=\{([^}]*)\}",
                                      op.rest)
                if branches:
                    names = re.findall(r"%?([\w.\-]+)", branches[0])
                    subs = [self.analyze(n) for n in names if n in self.comps]
                    if subs:
                        best = max(subs, key=lambda s: s["flops"] + s["bytes"])
                        flops += best["flops"]
                        bytes_ += best["bytes"]
            elif oc == "dot":
                flops += self._dot_flops(cname, op)
            elif oc == "convolution":
                flops += 2.0 * _elems_of(op.type_str)  # lower bound
            elif oc in _ELEMENTWISE:
                flops += _elems_of(op.type_str)
            elif oc == "reduce" or oc.startswith("reduce-window"):
                in_elems = sum(_elems_of(self.types[cname].get(o, ""))
                               for o in op.operands[: len(op.operands) // 2])
                flops += in_elems
            for kind in _COLLECTIVES:
                if oc == kind or oc.startswith(kind + "-"):
                    coll[kind] += _bytes_of(op.type_str)
            if sub and sub in self.comps:
                s = self.analyze(sub)
                flops += mult * s["flops"]
                bytes_ += mult * s["bytes"]
                for k in _COLLECTIVES:
                    coll[k] += mult * s[k]
            bytes_ += op_bytes
        out = {"flops": flops, "bytes": bytes_, **coll,
               "collective_bytes": sum(coll.values())}
        self._memo[cname] = out
        return out


def corrected_cost(hlo_text: str) -> dict:
    return HloCost(hlo_text).analyze()


def xla_cost_dict(compiled) -> dict:
    """``compiled.cost_analysis()``, ``{}`` where XLA reports none."""
    return compiled.cost_analysis() or {}
