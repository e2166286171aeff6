"""HLO-text cost analysis: per-kernel FLOPs / HBM bytes / collective bytes.

Why not ``compiled.cost_analysis()``?  Verified in this container: XLA's
aggregate cost analysis counts a ``while`` body (lax.scan over layers)
**once**, independent of trip count — a 94-layer scanned model would be
undercounted by ~94x.  This module parses the post-SPMD optimized HLO
(``compiled.as_text()``), multiplies loop bodies by the
``known_trip_count`` backend annotation, and models each *fusion as one
kernel*: HBM traffic = the fusion's operands + results (interior values
stay in registers/VMEM), FLOPs = sum over interior ops.

It also classifies every executed kernel into the paper's operator
taxonomy (GEMM / non-GEMM{memory, arith, norm} / SSM-specific /
collective) using ``jax.named_scope`` metadata preserved in
``metadata={op_name=...}`` — the same breakdown the paper extracts from
torch.profiler, derived analytically.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np


DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
    "token": 0, "opaque": 0,
}

MEMORY_OPS = {
    "reshape", "transpose", "copy", "slice", "dynamic-slice",
    "dynamic-update-slice", "concatenate", "gather", "scatter", "pad",
    "broadcast", "reverse", "bitcast-convert", "copy-start", "copy-done",
}
ARITH_OPS = {
    "add", "subtract", "multiply", "divide", "power", "exponential",
    "exponential-minus-one", "log", "log-plus-one", "rsqrt", "sqrt", "cbrt",
    "tanh", "logistic", "sine", "cosine", "negate", "abs", "sign", "floor",
    "ceil", "round-nearest-afz", "round-nearest-even", "maximum", "minimum",
    "compare", "select", "clamp", "and", "or", "xor", "not", "convert",
    "reduce", "reduce-window", "map", "iota", "rng", "rng-bit-generator",
    "erf", "atan2", "remainder", "shift-left", "shift-right-logical",
    "shift-right-arithmetic", "is-finite", "reduce-precision", "stochastic-convert",
}
COLLECTIVE_OPS = {
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast", "ragged-all-to-all",
    "all-reduce-start", "all-gather-start", "collective-permute-start",
}
ZERO_COST_OPS = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "token", "partition-id", "replica-id", "domain",
    "opt-barrier", "all-reduce-done", "all-gather-done",
    "collective-permute-done", "async-done", "custom-call",
}

# named_scope → paper operator class (priority order).  "decode_fused" is
# the serving decode-step recurrence (fused conv shift + SSM state update,
# src/repro/kernels/decode_fused/) — it IS the custom SSM kernel on the
# decode path, so its ops belong to the ssm family, not arith/memory.
SSM_SCOPES = ("ssd_core", "ssm_core", "conv1d", "ssm_gate", "decode_fused")
NORM_SCOPES = ("norm",)


@dataclass
class Op:
    name: str
    opcode: str
    shapes: List[Tuple[str, Tuple[int, ...]]]   # result (dtype, dims) list
    operands: List[str]
    attrs: str
    op_name: str = ""                           # metadata scope path

    def result_bytes(self) -> int:
        return sum(int(np.prod(d, dtype=np.int64)) * DTYPE_BYTES.get(t, 4)
                   for t, d in self.shapes)

    def result_elems(self) -> int:
        return sum(int(np.prod(d, dtype=np.int64)) for t, d in self.shapes)


@dataclass
class KernelCost:
    name: str
    opcode: str
    clazz: str
    scope: str
    flops: float = 0.0
    bytes: float = 0.0
    coll_bytes: float = 0.0     # per-device wire bytes
    count: float = 1.0          # loop-trip multiplier applied


@dataclass
class CostSummary:
    kernels: List[KernelCost] = field(default_factory=list)

    @property
    def flops(self) -> float:
        return sum(k.flops * k.count for k in self.kernels)

    @property
    def bytes(self) -> float:
        return sum(k.bytes * k.count for k in self.kernels)

    @property
    def coll_bytes(self) -> float:
        return sum(k.coll_bytes * k.count for k in self.kernels)

    def by_class(self) -> Dict[str, Dict[str, float]]:
        out: Dict[str, Dict[str, float]] = defaultdict(
            lambda: {"flops": 0.0, "bytes": 0.0, "coll_bytes": 0.0, "n": 0.0})
        for k in self.kernels:
            c = out[k.clazz]
            c["flops"] += k.flops * k.count
            c["bytes"] += k.bytes * k.count
            c["coll_bytes"] += k.coll_bytes * k.count
            c["n"] += k.count
        return dict(out)

    def by_scope(self, depth: int = 1) -> Dict[str, Dict[str, float]]:
        out: Dict[str, Dict[str, float]] = defaultdict(
            lambda: {"flops": 0.0, "bytes": 0.0})
        for k in self.kernels:
            scope = k.scope or "(unscoped)"
            c = out[scope]
            c["flops"] += k.flops * k.count
            c["bytes"] += k.bytes * k.count
        return dict(out)


_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_NAME_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*")
_OPCODE_RE = re.compile(r"\s*([\w\-]+)\(")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)(?:\s*\(.*\))?\s+->\s+.*\{")
_METADATA_RE = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')
_CALLS_RE = re.compile(r"calls=%?([\w\.\-]+)")
_BODY_RE = re.compile(r"body=%?([\w\.\-]+)")
_COND_RE = re.compile(r"condition=%?([\w\.\-]+)")
_TRIP_RE = re.compile(r'known_trip_count[^0-9]*"?n"?[^0-9]*(\d+)')
_OPERANDS_RE = re.compile(r"%([\w\.\-]+)")
_IOTA_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_LIST_GROUPS_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")


def _parse_shapes(type_str: str) -> List[Tuple[str, Tuple[int, ...]]]:
    out = []
    for m in _SHAPE_RE.finditer(type_str):
        dtype = m.group(1)
        if dtype not in DTYPE_BYTES:
            continue
        dims = tuple(int(x) for x in m.group(2).split(",") if x)
        out.append((dtype, dims))
    return out


def parse_hlo(text: str) -> Dict[str, List[Op]]:
    """Parse HLO text into {computation_name: [ops]}."""
    comps: Dict[str, List[Op]] = {}
    entry_name = None
    current: Optional[str] = None
    for line in text.splitlines():
        if current is None:
            m = _COMP_RE.match(line.strip())
            if m and line.rstrip().endswith("{"):
                current = m.group(1)
                comps[current] = []
                if line.startswith("ENTRY"):
                    entry_name = current
            continue
        if line.startswith("}") or line.strip() == "}":
            current = None
            continue
        m = _NAME_RE.match(line)
        if not m:
            continue
        name = m.group(1)
        rest = line[m.end():]
        # result type: tuple types "(f32[..], /*index=1*/ f32[..])" contain
        # parens and '=' (index comments) — scan to the matching ')'.
        if rest.startswith("("):
            depth = 0
            i = 0
            for i, ch in enumerate(rest):
                if ch == "(":
                    depth += 1
                elif ch == ")":
                    depth -= 1
                    if depth == 0:
                        break
            type_str, rest = rest[:i + 1], rest[i + 1:]
        else:
            sp = rest.find(" ")
            if sp < 0:
                continue
            type_str, rest = rest[:sp], rest[sp:]
        m2 = _OPCODE_RE.match(rest)
        if not m2:
            continue
        opcode = m2.group(1)
        rest = rest[m2.end():]
        # operands: up to the closing paren at depth 0
        depth, i = 1, 0
        while i < len(rest) and depth:
            if rest[i] == "(":
                depth += 1
            elif rest[i] == ")":
                depth -= 1
            i += 1
        operand_str, attrs = rest[:i], rest[i:]
        md = _METADATA_RE.search(line)
        comps[current].append(Op(
            name=name, opcode=opcode, shapes=_parse_shapes(type_str),
            operands=_OPERANDS_RE.findall(operand_str), attrs=attrs,
            op_name=md.group(1) if md else ""))
    if entry_name is not None:
        comps["__entry__"] = comps[entry_name]
    return comps


def _group_size(attrs: str, default: int = 1) -> int:
    m = _IOTA_GROUPS_RE.search(attrs)
    if m:
        return int(m.group(2))
    m = _LIST_GROUPS_RE.search(attrs)
    if m:
        ids = [x for x in m.group(1).split(",") if x.strip() != ""]
        return max(1, len(ids))
    return default


def _scope_of(op_name: str) -> str:
    """Last interesting named_scope component of the metadata path."""
    parts = [p for p in op_name.split("/") if p]
    known = SSM_SCOPES + NORM_SCOPES + (
        "attn_core", "attn_decode", "qkv_proj", "o_proj", "rope", "mlp",
        "moe_route",
        "moe_dispatch", "moe_expert", "moe_combine", "moe_shared_expert",
        "embed", "lm_head", "ssm_in_proj", "ssm_out_proj", "optimizer",
        "loss", "grad_compress")
    for p in reversed(parts):
        for k in known:
            # grad ops carry wrapped paths like "transpose(jvp(mlp))"
            if k in p:
                return k
    return parts[-1] if parts else ""


def _dot_flops(op: Op, shape_env: Dict[str, List[Tuple[str, Tuple[int, ...]]]]
               ) -> float:
    out_elems = op.result_elems()
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", op.attrs)
    contract = 1
    lhs_shapes = shape_env.get(op.operands[0]) if op.operands else None
    if m and lhs_shapes:
        dims = lhs_shapes[0][1]
        for idx in (int(x) for x in m.group(1).split(",") if x):
            if idx < len(dims):
                contract *= dims[idx]
    return 2.0 * out_elems * max(contract, 1)


def _conv_flops(op: Op, shape_env) -> float:
    out_elems = op.result_elems()
    m = re.search(r"window=\{size=([\dx]+)", op.attrs)
    ksize = 1
    if m:
        for x in m.group(1).split("x"):
            ksize *= int(x)
    rhs = shape_env.get(op.operands[1]) if len(op.operands) > 1 else None
    in_ch = rhs[0][1][-2] if rhs and len(rhs[0][1]) >= 2 else 1
    return 2.0 * out_elems * ksize * max(in_ch, 1)


class HloAnalyzer:
    def __init__(self, text: str):
        self.comps = parse_hlo(text)
        # shape env: op name -> result shapes (across all comps; names unique)
        self.shape_env: Dict[str, List[Tuple[str, Tuple[int, ...]]]] = {}
        for ops in self.comps.values():
            for op in ops:
                self.shape_env[op.name] = op.shapes
        self._flops_cache: Dict[str, float] = {}

    # -- interior FLOPs of a computation (fusion bodies, called comps) ------
    def _comp_flops(self, comp: str) -> float:
        if comp in self._flops_cache:
            return self._flops_cache[comp]
        self._flops_cache[comp] = 0.0   # cycle guard
        total = 0.0
        for op in self.comps.get(comp, []):
            total += self._op_interior_flops(op)
        self._flops_cache[comp] = total
        return total

    def _op_interior_flops(self, op: Op) -> float:
        oc = op.opcode
        if oc == "dot":
            return _dot_flops(op, self.shape_env)
        if oc == "convolution":
            return _conv_flops(op, self.shape_env)
        if oc == "fusion" or oc == "call":
            m = _CALLS_RE.search(op.attrs) or re.search(
                r"to_apply=%?([\w\.\-]+)", op.attrs)
            return self._comp_flops(m.group(1)) if m else 0.0
        if oc == "while":
            mb, mc = _BODY_RE.search(op.attrs), _COND_RE.search(op.attrs)
            mt = _TRIP_RE.search(op.attrs)
            trips = int(mt.group(1)) if mt else 1
            inner = 0.0
            if mb:
                inner += self._comp_flops(mb.group(1))
            if mc:
                inner += self._comp_flops(mc.group(1))
            return trips * inner
        if oc == "conditional":
            m = _BRANCHES_RE.search(op.attrs)
            if m:
                names = [x.strip().lstrip("%") for x in m.group(1).split(",")]
                return max((self._comp_flops(n) for n in names), default=0.0)
            return 0.0
        if oc in ARITH_OPS:
            return float(op.result_elems())
        return 0.0

    # -- operand bytes --------------------------------------------------------
    def _operand_bytes(self, op: Op) -> float:
        total = 0.0
        for name in op.operands:
            shapes = self.shape_env.get(name)
            if shapes:
                total += sum(int(np.prod(d, dtype=np.int64))
                             * DTYPE_BYTES.get(t, 4) for t, d in shapes)
        return total

    def _name_bytes(self, name: str) -> float:
        shapes = self.shape_env.get(name)
        if not shapes:
            return 0.0
        return sum(int(np.prod(d, dtype=np.int64)) * DTYPE_BYTES.get(t, 4)
                   for t, d in shapes)

    def _kernel_bytes(self, op: Op) -> float:
        """HBM traffic of one kernel.

        Two in-place/sparse-access patterns XLA handles that a naive
        operands+results sum over-charges by orders of magnitude:
          * dynamic-update-slice roots alias the big buffer — only the
            update slice moves;
          * fusion operands consumed ONLY by (dynamic-)slice/gather interior
            ops — only the slice results move.
        """
        if op.opcode == "dynamic-update-slice":
            upd = (self._name_bytes(op.operands[1])
                   if len(op.operands) > 1 else 0.0)
            return max(2.0 * upd, 1.0)
        if op.opcode != "fusion":
            return self._operand_bytes(op) + op.result_bytes()
        m = _CALLS_RE.search(op.attrs)
        interior = self.comps.get(m.group(1), []) if m else []
        if not interior:
            return self._operand_bytes(op) + op.result_bytes()
        params: Dict[str, int] = {}
        for io in interior:
            if io.opcode == "parameter":
                mi = re.match(r"param_(\d+)", io.name)
                if mi:
                    params[io.name] = int(mi.group(1))
        consumers: Dict[str, List[Op]] = {}
        for io in interior:
            for o in io.operands:
                consumers.setdefault(o, []).append(io)
        sliced: Dict[int, float] = {}
        for pname, idx in params.items():
            cons = consumers.get(pname, [])
            if cons and all(c.opcode in ("dynamic-slice", "slice", "gather")
                            for c in cons):
                sliced[idx] = sum(c.result_bytes() for c in cons)
        total = 0.0
        for i, oname in enumerate(op.operands):
            total += sliced[i] if i in sliced else self._name_bytes(oname)
        root = interior[-1]
        if root.opcode == "dynamic-update-slice":
            # in-place update: write = update slice only, and the aliased
            # full-buffer operand is not streamed — drop its read charge.
            upd = (self._name_bytes(root.operands[1])
                   if len(root.operands) > 1 else 0.0)
            total += upd
            for i, oname in enumerate(op.operands):
                if i in sliced:
                    continue
                if abs(self._name_bytes(oname) - op.result_bytes()) < 1:
                    total -= self._name_bytes(oname)
                    break
        else:
            total += op.result_bytes()
        return max(total, 1.0)

    # -- classification -------------------------------------------------------
    def _classify(self, op: Op) -> str:
        scope_path = op.op_name
        if any(s in scope_path for s in SSM_SCOPES):
            return "ssm"
        if op.opcode in COLLECTIVE_OPS:
            return "collective"
        if op.opcode in ("dot", "convolution"):
            return "gemm"
        if op.opcode in ("fusion", "call"):
            m = _CALLS_RE.search(op.attrs) or re.search(
                r"to_apply=%?([\w\.\-]+)", op.attrs)
            if m:
                interior = self.comps.get(m.group(1), [])
                if any(o.opcode in ("dot", "convolution") for o in interior):
                    return "gemm"
        if any(s in scope_path for s in NORM_SCOPES):
            return "norm"
        if op.opcode in MEMORY_OPS:
            return "memory"
        if op.opcode in ARITH_OPS:
            return "arith"
        if op.opcode == "fusion":
            m = _CALLS_RE.search(op.attrs)
            interior = self.comps.get(m.group(1), []) if m else []
            if any(o.opcode in ARITH_OPS for o in interior):
                return "arith"
            return "memory"
        return "other"

    # -- kernel walk ----------------------------------------------------------
    def _walk(self, comp: str, mult: float, out: List[KernelCost]) -> None:
        for op in self.comps.get(comp, []):
            oc = op.opcode
            if oc in ZERO_COST_OPS and oc not in COLLECTIVE_OPS:
                # custom-call: count bytes (conservative), no flops
                if oc == "custom-call":
                    out.append(KernelCost(
                        name=op.name, opcode=oc, clazz="other",
                        scope=_scope_of(op.op_name),
                        bytes=self._operand_bytes(op) + op.result_bytes(),
                        count=mult))
                continue
            if oc == "while":
                mb, mc = _BODY_RE.search(op.attrs), _COND_RE.search(op.attrs)
                mt = _TRIP_RE.search(op.attrs)
                trips = int(mt.group(1)) if mt else 1
                if mb:
                    self._walk(mb.group(1), mult * trips, out)
                if mc:
                    self._walk(mc.group(1), mult * trips, out)
                continue
            if oc == "call":
                m = re.search(r"to_apply=%?([\w\.\-]+)", op.attrs)
                if m:
                    self._walk(m.group(1), mult, out)
                continue
            if oc == "conditional":
                m = _BRANCHES_RE.search(op.attrs)
                if m:
                    names = [x.strip().lstrip("%") for x in m.group(1).split(",")]
                    costs = []
                    for n in names:
                        sub: List[KernelCost] = []
                        self._walk(n, mult, sub)
                        costs.append((sum(k.flops + k.bytes for k in sub), sub))
                    if costs:
                        out.extend(max(costs, key=lambda c: c[0])[1])
                continue
            clazz = self._classify(op)
            scope_name = op.op_name
            if not scope_name and op.opcode == "fusion":
                # XLA wrapper fusions (wrapped_*) drop metadata: inherit the
                # scope from interior ops
                m = _CALLS_RE.search(op.attrs)
                for io in (self.comps.get(m.group(1), []) if m else []):
                    if io.op_name:
                        scope_name = io.op_name
                        break
                if clazz in ("arith", "memory", "other"):
                    redo = self._classify(Op(op.name, op.opcode, op.shapes,
                                             op.operands, op.attrs,
                                             scope_name))
                    clazz = redo
            flops = self._op_interior_flops(op)
            byts = self._kernel_bytes(op)
            coll = 0.0
            if clazz == "collective":
                n = _group_size(op.attrs, default=2)
                opb = self._operand_bytes(op)
                outb = op.result_bytes()
                base = oc.replace("-start", "")
                if base == "all-gather":
                    coll = outb * (n - 1) / max(n, 1)
                elif base == "all-reduce":
                    coll = 2.0 * opb * (n - 1) / max(n, 1)
                elif base == "reduce-scatter":
                    coll = opb * (n - 1) / max(n, 1)
                elif base in ("all-to-all", "ragged-all-to-all"):
                    coll = opb * (n - 1) / max(n, 1)
                else:  # collective-permute / broadcast
                    coll = opb
            out.append(KernelCost(name=op.name, opcode=oc, clazz=clazz,
                                  scope=_scope_of(scope_name), flops=flops,
                                  bytes=byts, coll_bytes=coll, count=mult))

    def summarize(self) -> CostSummary:
        out: List[KernelCost] = []
        self._walk("__entry__", 1.0, out)
        return CostSummary(kernels=out)

    # -- fused-region analysis -------------------------------------------------
    # Models the deployed Pallas-kernel path: all ops sharing a fusable
    # named_scope within one computation become ONE kernel whose HBM bytes
    # are the region's external inputs + outputs (interior stays in VMEM),
    # exactly like the paper's fused `mamba_split_conv1d_scan_combined`.
    FUSABLE = ("attn_core", "ssd_core", "ssm_core", "conv1d", "ssm_gate",
               "norm", "rope")
    # the deployed mamba kernel fuses conv1d + scan + gate into ONE kernel
    # (mamba_split_conv1d_scan_combined) — model the same fusion boundary.
    SUPER_REGION = {"conv1d": "ssm_combined", "ssd_core": "ssm_combined",
                    "ssm_core": "ssm_combined", "ssm_gate": "ssm_combined"}

    def _region_scopes(self, scope: str) -> Tuple[str, ...]:
        region = self.SUPER_REGION.get(scope)
        if region is None:
            return (scope,)
        return tuple(s for s, r in self.SUPER_REGION.items() if r == region)

    def _region_bytes(self, comp: str, scope: str) -> Tuple[float, float]:
        ops = self.comps.get(comp, [])
        scopes = set(self._region_scopes(scope))
        member = {op.name for op in ops if _scope_of(op.op_name) in scopes}
        if not member:
            return 0.0, 0.0
        raw = 0.0
        io = 0.0
        consumed_outside = set()
        for op in ops:
            if op.name in member:
                continue
            for o in op.operands:
                if o in member:
                    consumed_outside.add(o)
        for op in ops:
            if op.name not in member:
                continue
            raw += self._operand_bytes(op) + op.result_bytes()
            for o in op.operands:
                if o not in member:
                    shapes = self.shape_env.get(o)
                    if shapes:
                        io += sum(int(np.prod(d, dtype=np.int64))
                                  * DTYPE_BYTES.get(t, 4) for t, d in shapes)
            if op.name in consumed_outside:
                io += op.result_bytes()
        # ROOT results count as outputs
        if ops and ops[-1].name in member and ops[-1].name not in consumed_outside:
            io += ops[-1].result_bytes()
        return raw, io

    def summarize_fused(self) -> CostSummary:
        """CostSummary with fusable scope-regions collapsed to single
        kernels (per computation, trip-count preserved)."""
        out: List[KernelCost] = []
        self._walk("__entry__", 1.0, out)
        # group kernels by (computation-agnostic) identity: recover the
        # computation of each op name
        op_comp: Dict[str, str] = {}
        for comp, ops in self.comps.items():
            if comp == "__entry__":
                continue
            for op in ops:
                op_comp[op.name] = comp
        region_cache: Dict[Tuple[str, str], Tuple[float, float]] = {}
        fused: Dict[Tuple[str, str], KernelCost] = {}
        rest: List[KernelCost] = []
        for k in out:
            if k.scope not in self.FUSABLE or k.clazz == "collective":
                rest.append(k)
                continue
            comp = op_comp.get(k.name, "")
            region = self.SUPER_REGION.get(k.scope, k.scope)
            key = (comp, region)
            if key not in region_cache:
                region_cache[key] = self._region_bytes(comp, k.scope)
            raw, io = region_cache[key]
            scale = io / raw if raw else 1.0
            if key not in fused:
                clazz = ("ssm" if (k.scope in SSM_SCOPES
                                   or region == "ssm_combined") else
                         "norm" if k.scope in NORM_SCOPES else "gemm")
                fused[key] = KernelCost(
                    name=f"fused_{region}", opcode="fused-region",
                    clazz=clazz, scope=region, count=k.count)
            fk = fused[key]
            fk.flops += k.flops * (k.count / fk.count)
            fk.bytes += k.bytes * scale * (k.count / fk.count)
        return CostSummary(kernels=rest + list(fused.values()))


def analyze_hlo_text(text: str) -> CostSummary:
    return HloAnalyzer(text).summarize()


def analyze_hlo_text_fused(text: str) -> CostSummary:
    return HloAnalyzer(text).summarize_fused()


def analyze_compiled(compiled) -> CostSummary:
    return analyze_hlo_text(compiled.as_text())
