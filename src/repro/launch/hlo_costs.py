"""HLO cost model with correct while-loop (scan) accounting.

XLA's HloCostAnalysis (what `compiled.cost_analysis()` reports) counts every
computation ONCE — a `jax.lax.scan` over 56 layers shows up as one layer's
flops. All our models scan over layers and all decode loops scan over steps,
so naive cost_analysis understates flops/bytes/collectives by up to ~n_layers.

This module parses `compiled.as_text()` (post-optimization, scheduled HLO) and
propagates costs through the call graph, multiplying `while` bodies by their
trip count (which XLA helpfully records in
``backend_config={"known_trip_count":{"n":...}}`` for counted loops).

Cost model per op (mirrors HloCostAnalysis conventions):
  flops:
    dot         2 * numel(result) * prod(lhs contracting dim sizes)
    elementwise 1 * numel(result)   (transcendentals included, like XLA)
    reduce      numel(operand)
    sort        numel * log2(numel) comparisons
  bytes accessed (HBM traffic model, post-fusion):
    each top-level op reads its operands and writes its result;
    fusion internals are VMEM-resident (not counted); free ops
    (tuple/gte/parameter/bitcast/constant) move nothing.
  collectives:
    result bytes, classified by kind; `-start` counted, `-done` skipped.

Everything is per-device (the partitioned module), matching the roofline
convention in DESIGN.md §7.
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass, field

# Published per-chip peaks, keyed by ``jax.Device.device_kind``. Source:
# Google Cloud TPU documentation, "TPU v5e" system architecture — 197
# TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of interchip
# interconnect over 4 links per chip.
DEVICE_PEAKS: dict[str, dict[str, float]] = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_link_bytes_per_s": 1600e9 / 8 / 4,
    },
}


def device_peaks(device_kind: str) -> dict[str, float]:
    """The peaks of ``device_kind``; a device not in the table is an error."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; add them,"
            " with their source, to DEVICE_PEAKS"
        ) from None


_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1, "s32": 4, "u32": 4,
    "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

COLLECTIVE_KINDS = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast", "ragged-all-to-all",
)

_FREE_OPS = {
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "after-all", "iota", "partition-id", "replica-id", "opt-barrier",
}

# ops that are pure data movement / control at top level: bytes yes, flops no
_ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "abs",
    "negate", "exponential", "exponential-minus-one", "log", "log-plus-one",
    "tanh", "rsqrt", "sqrt", "cbrt", "power", "atan2", "compare", "select",
    "and", "or", "xor", "not", "shift-left", "shift-right-logical",
    "shift-right-arithmetic", "floor", "ceil", "round-nearest-afz",
    "round-nearest-even", "sign", "is-finite", "clamp", "sine", "cosine",
    "tan", "erf", "logistic", "remainder", "stochastic-convert", "popcnt",
    "clz",
}


@dataclass
class Cost:
    flops: float = 0.0
    bytes: float = 0.0
    coll: dict = field(default_factory=dict)
    coll_ops: int = 0

    def __iadd__(self, other: "Cost") -> "Cost":
        self.flops += other.flops
        self.bytes += other.bytes
        for k, v in other.coll.items():
            self.coll[k] = self.coll.get(k, 0.0) + v
        self.coll_ops += other.coll_ops
        return self

    def scaled(self, m: float) -> "Cost":
        return Cost(
            self.flops * m,
            self.bytes * m,
            {k: v * m for k, v in self.coll.items()},
            int(self.coll_ops * m),
        )


# ------------------------------------------------------------- type parsing


def _shape_numel_bytes(type_str: str) -> tuple[float, float]:
    """'f32[128,128]{1,0}' -> (numel, bytes). Tuples sum their components."""
    numel_total = 0.0
    bytes_total = 0.0
    for m in re.finditer(r"([a-z][a-z0-9]*)\[([0-9,]*)\]", type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue  # token[], opaque[] etc.
        numel = 1.0
        for d in dims.split(","):
            if d:
                numel *= int(d)
        numel_total += numel
        bytes_total += numel * _DTYPE_BYTES[dt]
    return numel_total, bytes_total


def _split_type_rest(rhs: str) -> tuple[str, str]:
    """Split '  f32[2]{0} dot(...), attrs' -> ('f32[2]{0}', 'dot(...), attrs')."""
    rhs = rhs.lstrip()
    if rhs.startswith("("):
        depth = 0
        for i, ch in enumerate(rhs):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    return rhs[: i + 1], rhs[i + 1 :].lstrip()
    sp = rhs.index(" ")
    return rhs[:sp], rhs[sp + 1 :].lstrip()


_OP_RE = re.compile(r"^([\w\-]+)\(")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.+)$")
_COMP_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s+->\s+.+\s+\{")
_TRIP_RE = re.compile(r"known_trip_count[^0-9]*?\"?n\"?[^0-9]*?(\d+)")
_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")
_BODY_RE = re.compile(r"body=%?([\w.\-]+)")
_COND_RE = re.compile(r"condition=%?([\w.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")


@dataclass
class _Op:
    name: str
    opcode: str
    type_str: str
    operands: list
    attrs: str
    raw_operands: str = ""


def _parse_computations(hlo: str) -> dict[str, list[_Op]]:
    comps: dict[str, list[_Op]] = {}
    current: list[_Op] | None = None
    entry_alias = None
    for line in hlo.splitlines():
        if current is None:
            m = _COMP_RE.match(line)
            if m:
                name = m.group(2)
                current = comps.setdefault(name, [])
                if m.group(1):
                    entry_alias = name
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _DEF_RE.match(line)
        if not m:
            continue
        name, rhs = m.group(1), m.group(2)
        try:
            type_str, rest = _split_type_rest(rhs)
        except ValueError:
            continue
        om = _OP_RE.match(rest)
        if not om:
            continue
        opcode = om.group(1)
        # operand list: first balanced parens of rest
        depth = 0
        start = rest.index("(")
        end = start
        for i in range(start, len(rest)):
            if rest[i] == "(":
                depth += 1
            elif rest[i] == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        operand_str = rest[start + 1 : end]
        attrs = rest[end + 1 :]
        operands = re.findall(r"%([\w.\-]+)", operand_str)
        current.append(_Op(name, opcode, type_str, operands, attrs, operand_str))
    if entry_alias:
        comps["__entry__"] = comps[entry_alias]
    return comps


# ------------------------------------------------------------- cost walk


def _dot_flops(op: _Op, symbols: dict[str, str]) -> float:
    out_numel, _ = _shape_numel_bytes(op.type_str)
    k = 1.0
    m = _CONTRACT_RE.search(op.attrs)
    if m and op.operands:
        lhs_type = symbols.get(op.operands[0], "")
        dm = re.search(r"\[([0-9,]*)\]", lhs_type)
        if dm:
            dims = [int(d) for d in dm.group(1).split(",") if d]
            for ci in m.group(1).split(","):
                if ci and int(ci) < len(dims):
                    k *= dims[int(ci)]
    return 2.0 * out_numel * k


def _fusion_io_bytes(
    fusion_op: _Op, called_ops: list[_Op], outer_symbols: dict[str, str]
) -> tuple[float, float]:
    """(read, write) HBM bytes of a fusion, slice/update-aware.

    Reads: parameters whose ONLY uses are slicing ops (through
    bitcast/convert/reshape/copy chains) are charged the slice result bytes;
    parameters consumed only as the IN-PLACE BUFFER of a dynamic-update-slice
    (operand 0 — XLA aliases it) are charged nothing. This is the scan
    pattern: stacked-layer params are dynamic-sliced and ys-stacks are
    dynamic-update-sliced inside while-body fusions.

    Writes: tuple components that are dynamic-update-slice chains are charged
    the UPDATE size (the buffer is updated in place), not the buffer size.
    """
    param_name_by_idx: dict[int, str] = {}
    uses: dict[str, list[tuple[_Op, int]]] = {}
    by_name = {op.name: op for op in called_ops}
    inner_symbols = {op.name: op.type_str for op in called_ops}
    for op in called_ops:
        if op.opcode == "parameter":
            try:
                param_name_by_idx[int(op.raw_operands)] = op.name
            except ValueError:
                pass
        for pos, o in enumerate(op.operands):
            uses.setdefault(o, []).append((op, pos))

    _PASSTHROUGH = {"bitcast", "convert", "reshape", "copy", "transpose"}
    _SLICERS = {"dynamic-slice", "slice", "gather"}

    def read_bytes_of(name: str, depth: int = 0) -> float | None:
        """Bytes actually read from `name`, or None if fully read."""
        if depth > 4:
            return None
        total = 0.0
        for u, pos in uses.get(name, ()):  # no uses -> dead param, reads 0
            if u.opcode in _SLICERS:
                total += _shape_numel_bytes(u.type_str)[1]
            elif u.opcode == "dynamic-update-slice" and pos == 0:
                continue  # aliased in-place buffer: not read
            elif u.opcode in _PASSTHROUGH:
                sub = read_bytes_of(u.name, depth + 1)
                if sub is None:
                    return None
                total += sub
            else:
                return None
        return total

    reads = 0.0
    for i, operand in enumerate(fusion_op.operands):
        full = _shape_numel_bytes(outer_symbols.get(operand, ""))[1]
        pname = param_name_by_idx.get(i)
        if pname is None:
            reads += full
            continue
        sliced = read_bytes_of(pname)
        reads += full if sliced is None else min(sliced, full)

    # writes: resolve root (last op); tuples component-wise; DUS -> update size
    def write_bytes_of(name: str, depth: int = 0) -> float:
        op = by_name.get(name)
        if op is None or depth > 4:
            return 0.0
        if op.opcode == "dynamic-update-slice":
            if len(op.operands) > 1:
                return _shape_numel_bytes(
                    inner_symbols.get(op.operands[1], "")
                )[1]
            return _shape_numel_bytes(op.type_str)[1]
        if op.opcode in _PASSTHROUGH and op.operands:
            return write_bytes_of(op.operands[0], depth + 1)
        return _shape_numel_bytes(op.type_str)[1]

    if called_ops:
        root = called_ops[-1]
        if root.opcode == "tuple":
            writes = sum(write_bytes_of(o) for o in root.operands)
        else:
            writes = write_bytes_of(root.name)
    else:
        writes = _shape_numel_bytes(fusion_op.type_str)[1]
    return reads, writes


def _comp_cost(
    name: str,
    comps: dict[str, list[_Op]],
    memo: dict[str, Cost],
    stack: set,
    *,
    count_bytes: bool,
) -> Cost:
    """Cost of one computation. count_bytes=False inside fusions (VMEM)."""
    key = f"{name}|{count_bytes}"
    if key in memo:
        return memo[key]
    if name in stack or name not in comps:
        return Cost()
    stack.add(name)
    symbols = {op.name: op.type_str for op in comps[name]}
    total = Cost()
    for op in comps[name]:
        oc = op.opcode
        out_numel, out_bytes = _shape_numel_bytes(op.type_str)
        operand_bytes = sum(
            _shape_numel_bytes(symbols.get(o, ""))[1] for o in op.operands
        )
        c = Cost()
        if oc == "while":
            body = _BODY_RE.search(op.attrs)
            cond = _COND_RE.search(op.attrs)
            trip_m = _TRIP_RE.search(op.attrs)
            trip = int(trip_m.group(1)) if trip_m else 1
            inner = Cost()
            if body:
                inner += _comp_cost(
                    body.group(1), comps, memo, stack, count_bytes=count_bytes
                )
            if cond:
                inner += _comp_cost(
                    cond.group(1), comps, memo, stack, count_bytes=count_bytes
                )
            c += inner.scaled(trip)
        elif oc == "fusion":
            called = _CALLS_RE.search(op.attrs)
            if called:
                # flops from inside; bytes only at the fusion boundary
                inner = _comp_cost(
                    called.group(1), comps, memo, stack, count_bytes=False
                )
                c.flops += inner.flops
                c.coll_ops += inner.coll_ops
                for k, v in inner.coll.items():
                    c.coll[k] = c.coll.get(k, 0.0) + v
            if count_bytes:
                if called and called.group(1) in comps:
                    reads, writes = _fusion_io_bytes(
                        op, comps[called.group(1)], symbols
                    )
                else:
                    reads, writes = operand_bytes, out_bytes
                c.bytes += reads + writes
        elif oc in ("call", "async-start"):
            called = _CALLS_RE.search(op.attrs)
            if called:
                c += _comp_cost(
                    called.group(1), comps, memo, stack, count_bytes=count_bytes
                )
        elif oc == "conditional":
            branches = _BRANCHES_RE.search(op.attrs)
            if branches:
                names = re.findall(r"%?([\w.\-]+)", branches.group(1))
                worst = Cost()
                for bn in names:
                    bc = _comp_cost(bn, comps, memo, stack, count_bytes=count_bytes)
                    if bc.flops + bc.bytes > worst.flops + worst.bytes:
                        worst = bc
                c += worst
            if count_bytes:
                c.bytes += out_bytes
        else:
            base = oc[:-6] if oc.endswith("-start") else oc
            if base in COLLECTIVE_KINDS and not oc.endswith("-done"):
                c.coll[base] = c.coll.get(base, 0.0) + out_bytes
                c.coll_ops += 1
            if oc == "dot":
                c.flops += _dot_flops(op, symbols)
            elif oc == "convolution":
                # approx: 2 * numel(out) * numel(kernel) / out_channels
                kb = _shape_numel_bytes(
                    symbols.get(op.operands[1], "") if len(op.operands) > 1 else ""
                )[0]
                c.flops += 2.0 * out_numel * max(kb, 1.0) ** 0.5
            elif oc in _ELEMENTWISE:
                c.flops += out_numel
            elif oc in ("reduce", "reduce-window"):
                c.flops += sum(
                    _shape_numel_bytes(symbols.get(o, ""))[0] for o in op.operands
                )
            elif oc == "sort":
                n = max(out_numel, 2.0)
                c.flops += n * math.log2(n)
            if count_bytes and oc not in _FREE_OPS:
                if oc in ("dynamic-slice", "slice", "gather"):
                    # reads only the sliced/gathered region, not the operand
                    c.bytes += 2.0 * out_bytes
                elif oc in ("dynamic-update-slice", "scatter"):
                    # reads + writes only the update region (in-place alias)
                    upd_bytes = (
                        _shape_numel_bytes(symbols.get(op.operands[1], ""))[1]
                        if len(op.operands) > 1
                        else out_bytes
                    )
                    c.bytes += 2.0 * upd_bytes
                else:
                    c.bytes += operand_bytes + out_bytes
        total += c
    stack.discard(name)
    memo[key] = total
    return total


def parse_hlo_costs(hlo_text: str) -> dict:
    """Per-device costs of a compiled (partitioned) HLO module.

    Returns {"flops", "bytes", "collectives": {kind: bytes, "total", "n_ops"}}
    with while bodies scaled by their known trip counts.
    """
    comps = _parse_computations(hlo_text)
    memo: dict[str, Cost] = {}
    cost = _comp_cost("__entry__", comps, memo, set(), count_bytes=True)
    coll = dict(cost.coll)
    coll["total"] = sum(coll.values())
    coll["n_ops"] = cost.coll_ops
    for kind in COLLECTIVE_KINDS:
        coll.setdefault(kind, 0.0)
    return {"flops": cost.flops, "bytes": cost.bytes, "collectives": coll}
