"""Compiled-HLO cost model (DESIGN.md §7).

``compiled.cost_analysis()`` counts ``while`` bodies **once**, but every
interesting program here loops: ``scan`` over layers, ``lax.map`` over query
chunks, the DEG search loop.  This parser rebuilds the cost from the
optimized (post-SPMD) HLO text with loop bodies multiplied by their trip
counts:

* **FLOPs** — from ``dot`` / ``convolution`` ops (recursing into fusion
  subcomputations), 2 x prod(output) x contraction.
* **HBM bytes** — sum of operand+output bytes of *top-level* compute ops
  (fusions, dots, gathers, scatters, copies, DUS, collectives).  Fusion
  internals stay in registers/VMEM and are not traffic.  This is the
  standard "every materialized buffer crosses HBM once" approximation.
* **Collective bytes** — operand bytes of all-gather / all-reduce /
  reduce-scatter / all-to-all / collective-permute, reported per category.

Trip counts come from the largest integer constant in the loop condition
computation (exact for scan/fori/map-style loops, an upper bound for
data-dependent loops like the DEG search — the roofline rescales those with
measured hop counts).

All shapes in post-SPMD HLO are already **per-device**, so every number this
module emits is per-device.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Optional

_DTYPE_BYTES = {
    "pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1, "f8e4m3fnuz": 1,
    "f8e5m2fnuz": 1, "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
_COMP_RE = re.compile(r"^(ENTRY\s+)?%?([\w\.\-]+)\s+\(.*\)\s*->")
_NAME_RE = re.compile(r"^\s+(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*")
_OPCODE_RE = re.compile(r"\s*([\w\-]+)\(")
_OPERAND_RE = re.compile(r"%?([\w\.\-]+)")
_CALL_ATTR_RE = re.compile(r"(calls|condition|body|to_apply)=%?([\w\.\-]+)")
_CONST_RE = re.compile(r"constant\((\d+)\)")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute", "all-gather-start", "all-reduce-start",
               "collective-permute-start")

_TRAFFIC_OPS = ("fusion", "dot", "convolution", "gather", "scatter", "copy",
                "dynamic-update-slice", "dynamic-slice", "slice", "concatenate",
                "sort", "transpose", "reshape", "broadcast", "reduce", "rng",
                "iota", "pad", "custom-call", "select-and-scatter",
                "cholesky", "triangular-solve") + COLLECTIVES


def shape_bytes(type_str: str) -> float:
    """Total bytes of an HLO type string (tuples summed)."""
    total = 0.0
    for dt, dims in _SHAPE_RE.findall(type_str):
        b = _DTYPE_BYTES.get(dt)
        if b is None:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * b
    return total


def shape_dims(type_str: str) -> Optional[tuple]:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return None
    dims = m.group(2)
    return tuple(int(d) for d in dims.split(",")) if dims else ()


@dataclasses.dataclass
class Instr:
    name: str
    type_str: str
    opcode: str
    rest: str
    operands: list
    calls: dict       # attr -> computation name
    trip: Optional[int] = None   # known_trip_count from backend_config


@dataclasses.dataclass
class Computation:
    name: str
    instrs: list
    by_name: dict

    def out_bytes(self, name: str) -> float:
        i = self.by_name.get(name)
        return shape_bytes(i.type_str) if i else 0.0


def _split_operands(rest: str) -> tuple[str, str]:
    """Split 'operand-list), attrs...' respecting nesting."""
    depth = 0
    for i, ch in enumerate(rest):
        if ch == "(":
            depth += 1
        elif ch == ")":
            if depth == 0:
                return rest[:i], rest[i + 1:]
            depth -= 1
    return rest, ""


def parse_module(text: str) -> dict[str, Computation]:
    comps: dict[str, Computation] = {}
    cur: Optional[Computation] = None
    entry_name = None
    for line in text.splitlines():
        if not line.strip():
            continue
        m = _COMP_RE.match(line)
        if m and line.rstrip().endswith("{"):
            cur = Computation(m.group(2), [], {})
            comps[cur.name] = cur
            if m.group(1):
                entry_name = cur.name
            continue
        if line.startswith("}"):
            cur = None
            continue
        if cur is None:
            continue
        ins = _parse_instruction(line)
        if ins is None:
            continue
        cur.instrs.append(ins)
        cur.by_name[ins.name] = ins
    if entry_name is not None:
        comps["__entry__"] = comps[entry_name]
    return comps


def result_copies(text: str, dtype: str, dims: tuple
                  ) -> list[tuple[str, str]]:
    """(name, result type) of each ``copy`` instruction of a compiled module
    whose result is ``dtype[dims]``, in any layout or memory space (a
    relayout, or a staging copy on chip: ``S(1)`` in the type)."""
    return sorted({(i.name, i.type_str) for c in parse_module(text).values()
                   for i in c.instrs
                   if i.opcode == "copy" and i.type_str.startswith(dtype + "[")
                   and shape_dims(i.type_str) == tuple(dims)})


def _parse_operand_names(opsec: str) -> list:
    """Operand names from an operand list section.

    Newer XLA prints bare names (``%a, %b``); older releases print the full
    type inline (``f32[32,32]{1,0} %a``), so naive token matching picks up
    dtype/dim junk.  Split on top-level commas and keep the *last* token of
    each fragment — the operand name in both formats.
    """
    parts, depth, start = [], 0, 0
    for i, ch in enumerate(opsec):
        if ch in "({[":
            depth += 1
        elif ch in ")}]":
            depth -= 1
        elif ch == "," and depth == 0:
            parts.append(opsec[start:i])
            start = i + 1
    parts.append(opsec[start:])
    out = []
    for p in parts:
        toks = _OPERAND_RE.findall(p)
        if toks:
            out.append(toks[-1])
    return out


def _parse_instruction(line: str) -> Optional[Instr]:
    m = _NAME_RE.match(line)
    if not m:
        return None
    name = m.group(1)
    rhs = line[m.end():]
    # result type: either a balanced (tuple, ...) or one dtype[dims]{layout}
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
        type_str, rhs = rhs[: end + 1], rhs[end + 1:]
    else:
        sp = rhs.find(" ")
        if sp == -1:
            return None
        type_str, rhs = rhs[:sp], rhs[sp:]
    mo = _OPCODE_RE.match(rhs)
    if not mo:
        return None
    opcode = mo.group(1)
    rest = rhs[mo.end():]
    opsec, attrs = _split_operands(rest)
    opsec = re.sub(r"/\*.*?\*/", "", opsec)   # strip /*index=N*/ comments
    operands = _parse_operand_names(opsec)
    calls = {k: v for k, v in _CALL_ATTR_RE.findall(attrs)}
    mt = _TRIP_RE.search(attrs)
    trip = int(mt.group(1)) if mt else None
    return Instr(name, type_str.strip(), opcode, attrs, operands, calls, trip)


@dataclasses.dataclass
class CostReport:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    collective_bytes: float = 0.0
    per_collective: dict = dataclasses.field(default_factory=dict)
    while_detail: list = dataclasses.field(default_factory=list)

    def merged(self, other: "CostReport", scale: float = 1.0) -> "CostReport":
        pc = dict(self.per_collective)
        for k, v in other.per_collective.items():
            pc[k] = pc.get(k, 0.0) + v * scale
        return CostReport(
            flops=self.flops + other.flops * scale,
            hbm_bytes=self.hbm_bytes + other.hbm_bytes * scale,
            collective_bytes=self.collective_bytes
            + other.collective_bytes * scale,
            per_collective=pc,
            while_detail=self.while_detail + other.while_detail,
        )


class HloCost:
    """Whole-module cost with while-trip scaling."""

    def __init__(self, text: str,
                 trip_overrides: Optional[dict[str, int]] = None):
        self.text = text
        self.comps = parse_module(text)
        self.trip_overrides = trip_overrides or {}
        self._const_cache: dict[str, int] = {}
        self._memo: dict[str, CostReport] = {}

    # -- trip counts -----------------------------------------------------
    def trip_count(self, cond_name: str) -> int:
        if cond_name in self.trip_overrides:
            return self.trip_overrides[cond_name]
        if cond_name in self._const_cache:
            return self._const_cache[cond_name]
        # largest integer constant in the condition computation's text block
        block = self._comp_text(cond_name)
        consts = [int(x) for x in _CONST_RE.findall(block)]
        trip = max(consts) if consts else 1
        self._const_cache[cond_name] = trip
        return trip

    def _comp_text(self, name: str) -> str:
        # cheap: find the block by header
        pat = re.compile(r"^(ENTRY\s+)?%?" + re.escape(name) + r"\s+\(",
                         re.M)
        m = pat.search(self.text)
        if not m:
            return ""
        start = m.start()
        end = self.text.find("\n}", start)
        return self.text[start:end] if end != -1 else self.text[start:]

    # -- flops of a dot instruction --------------------------------------
    def _dot_flops(self, comp: Computation, ins: Instr) -> float:
        out = shape_dims(ins.type_str)
        if out is None:
            return 0.0
        n_out = 1
        for d in out:
            n_out *= d
        # contraction size: product of lhs contracting dims
        mdims = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", ins.rest)
        lhs = comp.by_name.get(ins.operands[0]) if ins.operands else None
        k = 1
        if mdims and lhs is not None:
            ldims = shape_dims(lhs.type_str) or ()
            for ci in mdims.group(1).split(","):
                if ci and int(ci) < len(ldims):
                    k *= ldims[int(ci)]
        return 2.0 * n_out * k

    # -- per-computation cost --------------------------------------------
    def comp_cost(self, name: str, top_level: bool = True) -> CostReport:
        key = f"{name}|{top_level}"
        if key in self._memo:
            return self._memo[key]
        comp = self.comps.get(name)
        rep = CostReport()
        if comp is None:
            self._memo[key] = rep
            return rep
        for ins in comp.instrs:
            out_b = shape_bytes(ins.type_str)
            if ins.opcode == "while":
                body = ins.calls.get("body")
                cond = ins.calls.get("condition")
                if body in self.trip_overrides:
                    trip = self.trip_overrides[body]
                elif ins.trip is not None:       # XLA known_trip_count
                    trip = ins.trip
                else:
                    trip = self.trip_count(cond) if cond else 1
                body_rep = self.comp_cost(body, True) if body else CostReport()
                rep = rep.merged(body_rep, scale=trip)
                rep.while_detail.append(
                    {"body": body, "trip": trip,
                     "flops": body_rep.flops, "hbm": body_rep.hbm_bytes,
                     "coll": body_rep.collective_bytes})
                continue
            if ins.opcode in ("call", "conditional", "async-start"):
                for cn in ins.calls.values():
                    rep = rep.merged(self.comp_cost(cn, True))
                continue
            if ins.opcode == "fusion":
                callee = ins.calls.get("calls")
                if callee:
                    inner = self._fusion_flops(callee)
                    rep.flops += inner
                if top_level:
                    rep.hbm_bytes += self._fusion_traffic(comp, ins)
                continue
            if ins.opcode in ("dynamic-slice", "gather"):
                if top_level:
                    rep.hbm_bytes += 2.0 * out_b    # rows read + write only
                continue
            if ins.opcode == "dynamic-update-slice":
                if top_level:
                    upd = (comp.out_bytes(ins.operands[1])
                           if len(ins.operands) > 1 else out_b)
                    rep.hbm_bytes += 2.0 * upd      # aliased accumulator
                continue
            if ins.opcode in ("dot", "convolution"):
                rep.flops += self._dot_flops(comp, ins)
                if top_level:
                    rep.hbm_bytes += out_b + self._operand_bytes(comp, ins)
                continue
            if ins.opcode in COLLECTIVES:
                b = self._operand_bytes(comp, ins)
                rep.collective_bytes += b
                cat = ins.opcode.replace("-start", "")
                rep.per_collective[cat] = rep.per_collective.get(cat, 0) + b
                if top_level:
                    rep.hbm_bytes += out_b + b
                continue
            if top_level and ins.opcode in _TRAFFIC_OPS:
                rep.hbm_bytes += out_b + self._operand_bytes(comp, ins)
        self._memo[key] = rep
        return rep

    def _operand_bytes(self, comp: Computation, ins: Instr) -> float:
        return sum(comp.out_bytes(o) for o in ins.operands
                   if o in comp.by_name)

    def _fusion_traffic(self, comp: Computation, ins: Instr) -> float:
        """Slice-aware HBM traffic of a fusion.

        A fusion parameter consumed *only* by dynamic-slice reads just the
        slice (scan stacks are read this way in backward bodies); a root
        dynamic-update-slice into an aliased accumulator writes just the
        update (scan stacks are written this way in forward bodies).
        Everything else reads/writes its full size.
        """
        out_b = shape_bytes(ins.type_str)
        callee = self.comps.get(ins.calls.get("calls", ""))
        if callee is None:
            return out_b + self._operand_bytes(comp, ins)
        # map parameter index -> callee instruction
        pname = {}
        for ci in callee.instrs:
            if ci.opcode == "parameter" and ci.operands:
                try:
                    pname[int(ci.operands[0])] = ci.name
                except ValueError:
                    pass
        read = 0.0
        for i, o in enumerate(ins.operands):
            full = comp.out_bytes(o)
            if full <= 0:
                continue
            par = pname.get(i)
            if par is None:
                read += full
                continue
            consumers = self._terminal_consumers(callee, par)
            sliced_ops = ("dynamic-slice", "dynamic-update-slice", "gather")
            if consumers and all(cj.opcode in sliced_ops
                                 for cj, _ in consumers):
                eff = 0.0
                for cj, via in consumers:
                    if cj.opcode in ("dynamic-slice", "gather"):
                        # reads only the addressed rows (gather traffic =
                        # output bytes; matters for embedding lookups and
                        # the DEG neighbor gathers, which otherwise count
                        # the whole table as read)
                        eff += shape_bytes(cj.type_str)
                    else:  # DUS: accumulator operand is aliased, updates
                        if cj.operands and cj.operands[0] == via:
                            eff += (callee.out_bytes(cj.operands[1])
                                    if len(cj.operands) > 1 else 0.0)
                        else:
                            eff += full
                read += min(eff, full)
            else:
                read += full
        # write side: if the fusion output is a DUS into a same-shape aliased
        # accumulator, only the update is written (compare dims, not bytes:
        # the CPU backend inserts dtype converts around the DUS)
        write = out_b
        out_dims = shape_dims(ins.type_str)
        for cj in callee.instrs:
            if (cj.opcode == "dynamic-update-slice"
                    and shape_dims(cj.type_str) == out_dims
                    and len(cj.operands) > 1):
                write = callee.out_bytes(cj.operands[1])
                break
        return read + write

    _PASSTHROUGH = ("convert", "bitcast", "copy")

    def _terminal_consumers(self, comp: Computation, name: str,
                            depth: int = 0) -> list:
        """Consumers of ``name`` inside ``comp``, looking through dtype
        converts/bitcasts (the CPU backend wraps scan-stack DUS/DS in
        converts).  Returns [(instr, via_operand_name)]."""
        if depth > 4:
            return []
        out = []
        for cj in comp.instrs:
            if cj.opcode == "parameter" or name not in cj.operands:
                continue
            if cj.opcode in self._PASSTHROUGH:
                nested = self._terminal_consumers(comp, cj.name, depth + 1)
                out += nested or [(cj, name)]
            else:
                out.append((cj, name))
        return out

    def _fusion_flops(self, callee: str) -> float:
        comp = self.comps.get(callee)
        if comp is None:
            return 0.0
        total = 0.0
        for ins in comp.instrs:
            if ins.opcode in ("dot", "convolution"):
                total += self._dot_flops(comp, ins)
            elif ins.opcode == "fusion" and "calls" in ins.calls:
                total += self._fusion_flops(ins.calls["calls"])
        return total

    def entry_cost(self) -> CostReport:
        return self.comp_cost("__entry__", True)


def analyze_text(text: str,
                 trip_overrides: Optional[dict[str, int]] = None) -> dict:
    """Convenience: HLO text -> plain-dict cost summary (per device)."""
    hc = HloCost(text, trip_overrides)
    rep = hc.entry_cost()
    return {
        "flops": rep.flops,
        "hbm_bytes": rep.hbm_bytes,
        "collective_bytes": rep.collective_bytes,
        "per_collective": rep.per_collective,
        "while_detail": rep.while_detail,
    }
