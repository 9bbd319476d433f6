"""While-aware cost model over compiled HLO text.

XLA's built-in ``compiled.cost_analysis()`` counts a ``while`` body ONCE,
which under-counts scan-over-layers models by ~n_layers ×.  This walker
parses the compiled module text, multiplies loop bodies by their
``known_trip_count`` backend-config (falling back to the comparison constant
in the loop condition), and produces:

  flops       — dot ops: 2·|result|·|contracted|; arithmetic elementwise: |result|
  hbm_bytes   — fusion/op boundary traffic: operand + result bytes of
                top-level (non-fused) instructions — a *post-fusion* HBM
                traffic model, closer to reality than cost_analysis's
                per-op accounting
  collectives — ring-model per-device bytes (ag→result, ar→2·operand,
                rs→operand, a2a→operand, cp→result), trip-multiplied

All quantities are per-device (the SPMD module is the per-device program).
Validated against cost_analysis() on scan-free modules in
tests/test_hlo_cost.py.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "s4": 1, "u4": 1, "pred": 1, "c64": 8, "c128": 16,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e4m3b11fnuz": 1,
    "f8e5m2fnuz": 1, "f8e4m3fnuz": 1, "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([0-9,]*)\]")

# instruction: [ROOT] %name = <shape> opcode(...)
# tuple shapes contain spaces and /*index=N*/ comments but never nested parens
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*((?:\([^()]*\))|(?:\S+))\s+"
    r"([a-z][a-z0-9\-]*)\(")

_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->\s*.*\{\s*$")

_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")
_BODY_RE = re.compile(r"body=%?([\w.\-]+)")
_COND_RE = re.compile(r"condition=%?([\w.\-]+)")
_OPERAND_NAME_RE = re.compile(r"%([\w.\-]+)")
_LHS_C_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_LHS_B_RE = re.compile(r"lhs_batch_dims=\{([0-9,]*)\}")

_ARITH_OPS = {
    "add", "subtract", "multiply", "divide", "power", "maximum", "minimum",
    "tanh", "exponential", "log", "rsqrt", "sqrt", "negate", "abs", "sign",
    "compare", "select", "and", "or", "xor", "not", "atan2", "floor", "ceil",
    "round-nearest-afz", "round-nearest-even", "clamp", "remainder", "cosine",
    "sine", "logistic", "expm1", "log1p", "erf", "cbrt", "shift-left",
    "shift-right-logical", "shift-right-arithmetic", "clz", "popcnt",
}
_REDUCE_OPS = {"reduce", "reduce-window"}
_ZERO_BYTE_OPS = {
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "after-all", "add-dependency", "partition-id", "replica-id", "iota",
}
_COLLECTIVES = {"all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute"}


def shape_elems(shape_str: str) -> int:
    total = 0
    for _dt, dims in _SHAPE_RE.findall(shape_str):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n
    return total


def shape_bytes(shape_str: str, f32_bytes: int = 4) -> int:
    """XLA:CPU legalizes bf16 compute to f32, so byte counts on the CPU
    dry-run are 2× the TPU reality for every bf16-typed tensor.  Passing
    ``f32_bytes=2`` restores production (bf16-on-TPU) sizing; genuinely-f32
    tensors (optimizer state, fp32 grad accumulators) are then under-counted
    2×, a <1% effect quantified in EXPERIMENTS.md §Dry-run."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        b = f32_bytes if dt == "f32" else _DTYPE_BYTES[dt]
        total += n * b
    return total


def shape_dims(shape_str: str) -> List[int]:
    m = _SHAPE_RE.search(shape_str)
    if not m or not m.group(2):
        return []
    return [int(d) for d in m.group(2).split(",")]


@dataclass
class Instr:
    name: str
    shape: str
    opcode: str
    rest: str            # everything after the opcode's '('
    is_root: bool = False


@dataclass
class Cost:
    flops: float = 0.0
    hbm_bytes: float = 0.0        # upper bound: CPU fusion granularity
    hbm_bytes_ideal: float = 0.0  # lower bound: perfect elementwise fusion
    transcendentals: float = 0.0
    coll_bytes: Dict[str, float] = field(default_factory=dict)
    coll_count: Dict[str, float] = field(default_factory=dict)

    def __add__(self, o: "Cost") -> "Cost":
        cb = dict(self.coll_bytes)
        cc = dict(self.coll_count)
        for k, v in o.coll_bytes.items():
            cb[k] = cb.get(k, 0.0) + v
        for k, v in o.coll_count.items():
            cc[k] = cc.get(k, 0.0) + v
        return Cost(self.flops + o.flops, self.hbm_bytes + o.hbm_bytes,
                    self.hbm_bytes_ideal + o.hbm_bytes_ideal,
                    self.transcendentals + o.transcendentals, cb, cc)

    def __mul__(self, k: float) -> "Cost":
        return Cost(self.flops * k, self.hbm_bytes * k,
                    self.hbm_bytes_ideal * k,
                    self.transcendentals * k,
                    {n: v * k for n, v in self.coll_bytes.items()},
                    {n: v * k for n, v in self.coll_count.items()})

    @property
    def collective_bytes(self) -> float:
        return sum(self.coll_bytes.values())


class HloModule:
    def __init__(self, text: str, f32_bytes: int = 4):
        self.computations: Dict[str, List[Instr]] = {}
        self.entry: Optional[str] = None
        self.f32_bytes = f32_bytes
        self._parse(text)
        self._memo: Dict[Tuple[str, bool], Cost] = {}

    def _bytes(self, shape_str: str) -> int:
        return shape_bytes(shape_str, self.f32_bytes)

    def _root_opcode(self, comp: str) -> Optional[str]:
        instrs = self.computations.get(comp, [])
        for i in instrs:
            if i.is_root:
                return i.opcode
        return instrs[-1].opcode if instrs else None

    def _contains_dot(self, comp: str) -> bool:
        return any(i.opcode in ("dot", "convolution")
                   for i in self.computations.get(comp, []))

    # ------------------------------------------------------------------
    def _parse(self, text: str) -> None:
        cur: Optional[str] = None
        for line in text.splitlines():
            if cur is None:
                m = _COMP_HDR_RE.match(line)
                if m:
                    cur = m.group(1)
                    self.computations[cur] = []
                    if line.lstrip().startswith("ENTRY"):
                        self.entry = cur
                continue
            if line.startswith("}") or line.strip() == "}":
                cur = None
                continue
            m = _INSTR_RE.match(line)
            if m:
                name, shape, opcode = m.group(1), m.group(2), m.group(3)
                rest = line[m.end():]
                self.computations[cur].append(
                    Instr(name, shape, opcode, rest,
                          is_root="ROOT" in line[:line.find("=")]))

    # ------------------------------------------------------------------
    def _operand_shapes(self, instr: Instr, symtab: Dict[str, str]) -> List[str]:
        # operand names appear before attribute section; attributes also use
        # %names (calls=, body=) — cut at the closing paren of the arg list.
        depth, i = 1, 0
        s = instr.rest
        while i < len(s) and depth:
            if s[i] == "(":
                depth += 1
            elif s[i] == ")":
                depth -= 1
            i += 1
        arglist = s[:i]
        return [symtab[n] for n in _OPERAND_NAME_RE.findall(arglist)
                if n in symtab]

    def _dot_flops(self, instr: Instr, symtab: Dict[str, str]) -> float:
        out_elems = shape_elems(instr.shape)
        ops = self._operand_shapes(instr, symtab)
        if not ops:
            return 0.0
        lhs_dims = shape_dims(ops[0])
        m = _LHS_C_RE.search(instr.rest)
        contracted = 1
        if m and m.group(1):
            for d in m.group(1).split(","):
                contracted *= lhs_dims[int(d)] if int(d) < len(lhs_dims) else 1
        return 2.0 * out_elems * contracted

    def _trip_count(self, instr: Instr) -> float:
        m = _TRIP_RE.search(instr.rest)
        if m:
            return float(m.group(1))
        # fallback: constant in the loop condition
        cond = _COND_RE.search(instr.rest)
        if cond and cond.group(1) in self.computations:
            for ci in self.computations[cond.group(1)]:
                if ci.opcode == "constant":
                    mm = re.search(r"constant\((\d+)\)", "constant(" + ci.rest)
                    if mm:
                        return float(mm.group(1))
        return 1.0

    # ------------------------------------------------------------------
    def computation_cost(self, name: str, fused: bool = False) -> Cost:
        key = (name, fused)
        if key in self._memo:
            return self._memo[key]
        total = Cost()
        symtab = {i.name: i.shape for i in self.computations.get(name, [])}
        for instr in self.computations.get(name, []):
            total = total + self._instr_cost(instr, symtab, fused)
        self._memo[key] = total
        return total

    def _instr_cost(self, instr: Instr, symtab: Dict[str, str],
                    fused: bool) -> Cost:
        op = instr.opcode
        c = Cost()
        base = op[:-6] if op.endswith("-start") else op
        if base in _COLLECTIVES:
            rbytes = self._bytes(instr.shape)
            obytes = sum(self._bytes(s) for s in
                         self._operand_shapes(instr, symtab))
            if base == "all-gather":
                b = rbytes
            elif base == "all-reduce":
                b = 2 * obytes
            elif base in ("reduce-scatter", "all-to-all"):
                b = obytes
            else:
                b = rbytes
            c.coll_bytes[base] = c.coll_bytes.get(base, 0.0) + b
            c.coll_count[base] = c.coll_count.get(base, 0.0) + 1
            c.hbm_bytes += rbytes + obytes
            c.hbm_bytes_ideal += rbytes + obytes
            return c
        if op.endswith("-done"):
            return c

        if op == "while":
            body = _BODY_RE.search(instr.rest)
            cond = _COND_RE.search(instr.rest)
            trips = self._trip_count(instr)
            if body:
                c = c + self.computation_cost(body.group(1)) * trips
            if cond:
                c = c + self.computation_cost(cond.group(1)) * trips
            return c
        if op in ("call", "async-start"):
            m = _CALLS_RE.search(instr.rest)
            if m:
                c = c + self.computation_cost(m.group(1))
            return c
        if op == "conditional":
            for m in re.finditer(r"(?:true_computation|false_computation|"
                                 r"branch_computations)=\{?%?([\w.\-, %]+)",
                                 instr.rest):
                for nm in _OPERAND_NAME_RE.findall("%" + m.group(1)):
                    if nm in self.computations:
                        c = c + self.computation_cost(nm)
            return c
        if op == "fusion":
            m = _CALLS_RE.search(instr.rest)
            root = None
            if m:
                inner = self.computation_cost(m.group(1), fused=True)
                root = self._root_opcode(m.group(1))
                c.flops += inner.flops
                c.transcendentals += inner.transcendentals
                for k, v in inner.coll_bytes.items():
                    c.coll_bytes[k] = c.coll_bytes.get(k, 0.0) + v
                for k, v in inner.coll_count.items():
                    c.coll_count[k] = c.coll_count.get(k, 0.0) + v
            obytes = [self._bytes(s) for s in self._operand_shapes(instr, symtab)]
            if root in ("dynamic-update-slice", "scatter"):
                # output aliases the big buffer operand: traffic is ~2× the
                # update, not the whole buffer
                small = sum(obytes) - (max(obytes) if obytes else 0)
                c.hbm_bytes += 2 * small
                c.hbm_bytes_ideal += 2 * small
            else:
                io = self._bytes(instr.shape) + sum(obytes)
                c.hbm_bytes += io
                if m and self._contains_dot(m.group(1)):
                    c.hbm_bytes_ideal += io
            return c

        # plain instruction
        if op == "dot":
            c.flops += self._dot_flops(instr, symtab)
        elif op == "convolution":
            # rough: 2 · |out| · |kernel_spatial·in_features| — parse kernel
            ops = self._operand_shapes(instr, symtab)
            kernel = shape_elems(ops[1]) if len(ops) > 1 else 1
            out = shape_dims(instr.shape)
            feat = out[-1] if out else 1
            c.flops += 2.0 * shape_elems(instr.shape) * max(kernel // max(feat, 1), 1)
        elif op in _ARITH_OPS:
            c.flops += shape_elems(instr.shape)
            if op in ("tanh", "exponential", "log", "logistic", "power",
                      "cosine", "sine", "expm1", "log1p", "erf"):
                c.transcendentals += shape_elems(instr.shape)
        elif op in _REDUCE_OPS:
            ops = self._operand_shapes(instr, symtab)
            c.flops += max((shape_elems(s) for s in ops[:1]), default=0)
        elif op in ("scatter", "gather", "dynamic-update-slice",
                    "dynamic-slice", "sort"):
            c.flops += shape_elems(instr.shape)

        if not fused and op not in _ZERO_BYTE_OPS:
            obytes = [self._bytes(s) for s in self._operand_shapes(instr, symtab)]
            if op in ("dynamic-update-slice", "scatter"):
                # in-place update: buffer operand aliases the output
                small = sum(obytes) - (max(obytes) if obytes else 0)
                c.hbm_bytes += 2 * small
                c.hbm_bytes_ideal += 2 * small
            elif op in ("dynamic-slice", "gather"):
                # reads ~result-size window out of a big operand
                c.hbm_bytes += 2 * self._bytes(instr.shape)
                c.hbm_bytes_ideal += 2 * self._bytes(instr.shape)
            else:
                io = self._bytes(instr.shape) + sum(obytes)
                c.hbm_bytes += io
                if op in ("dot", "convolution"):
                    c.hbm_bytes_ideal += io
        return c

    # ------------------------------------------------------------------
    def entry_cost(self) -> Cost:
        assert self.entry is not None, "no ENTRY computation found"
        return self.computation_cost(self.entry)


def analyze(hlo_text: str, f32_bytes: int = 4) -> Cost:
    return HloModule(hlo_text, f32_bytes=f32_bytes).entry_cost()
