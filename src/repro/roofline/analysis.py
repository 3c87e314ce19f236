"""Roofline analysis: analytic terms from the compiled dry-run artifact,
plus a *measured* mode (bytes and seconds observed on a live run against a
backend-configurable chip spec).

Analytic terms (seconds, per step), against a ``ChipSpec``:
  compute    = FLOPs / (chips * spec.peak_flops)
  memory     = HBM bytes / (chips * spec.hbm_bw)
  collective = per-device collective bytes / spec.link_bw

FLOPs / HBM bytes come from the analytic model (roofline/flops.py) because
XLA cost_analysis counts while(=scan) bodies once (measured);
raw cost_analysis values are recorded alongside.  Collective bytes are
parsed from ``compiled.as_text()`` -- the post-SPMD per-device program -- by
summing operand sizes of all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute, each multiplied by the product of enclosing
while-loop trip counts (extracted from the loop condition's comparison
constant).

Measured mode (``measured_roofline``) takes a wall time and the modeled
flops/bytes of the program that ran, and reports the achieved fraction of
the chip's roofline: ``max(compute_s, memory_s, collective_s) / time_s``
-- 1.0 means the run sits ON the roofline for its dominant resource.  The
peaks come from ``CHIP_PEAKS``, keyed by the ``device_kind`` JAX reports;
a device missing from the table is an error, and a host-backend timing
has no roofline at all (``roofline_summary`` reports it "not measured").
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """Peak rates of one accelerator chip (or host core) for roofline
    normalization.  ``link_bw`` is the per-link interconnect rate used by
    the collective term; hosts without a fabric reuse memory bandwidth."""
    name: str
    peak_flops: float          # FLOP/s per chip (dense, preferred dtype)
    hbm_bw: float              # bytes/s per chip
    link_bw: float             # bytes/s per link

    def as_dict(self):
        return dataclasses.asdict(self)


#: Published per-chip peaks, keyed by ``jax.Device.device_kind``.
#: TPU v5e -- Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
#: 819 GB/s HBM, 1,600 Gbit/s chip-to-chip interconnect (four links,
#: 50 GB/s each).
CHIP_PEAKS: Dict[str, ChipSpec] = {
    "TPU v5 lite": ChipSpec("tpu_v5e", 197e12, 819e9, 50e9),
}

#: the analytic dry-run's target chip
TPU_V5E = CHIP_PEAKS["TPU v5 lite"]

NOT_MEASURED = "not measured"


def chip_spec(device_kind: str) -> ChipSpec:
    """Published peaks of ``device_kind``; an unknown kind is an error."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}: add them to CHIP_PEAKS with "
                         f"their source") from None


def device_chip_spec(device=None) -> Optional[ChipSpec]:
    """Peaks of the device a measurement runs on (default: the first JAX
    device): None on the CPU backend, whose timings have no roofline."""
    if device is None:
        import jax
        device = jax.devices()[0]
    if device.platform == "cpu":
        return None
    return chip_spec(device.device_kind)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16, "s4": 1, "u4": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

def dtype_bytes(dtype: str) -> int:
    """Bytes per element of an HLO/numpy-style dtype name ("f32", "bf16",
    "bfloat16", "float32", ...).  The ONE bytes-per-dtype table -- the
    measured-mode byte models in ``benchmarks/`` use this instead of
    hardcoding 4."""
    alias = {"float64": "f64", "float32": "f32", "bfloat16": "bf16",
             "float16": "f16", "int64": "s64", "int32": "s32",
             "int16": "s16", "int8": "s8", "uint64": "u64", "uint32": "u32",
             "uint16": "u16", "uint8": "u8", "bool": "pred"}
    key = alias.get(str(dtype), str(dtype))
    if key not in _DTYPE_BYTES:
        raise KeyError(f"unknown dtype {dtype!r}")
    return _DTYPE_BYTES[key]


_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(.+?)\s+([\w\-]+)\((.*)$")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->.*\{")


def shape_bytes(type_str: str) -> int:
    """Total bytes of possibly-tuple HLO type string."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.groups()
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class CollectiveStats:
    bytes_by_kind: Dict[str, float]
    count_by_kind: Dict[str, int]
    total_bytes: float
    unresolved_trips: int = 0


def _parse_computations(text: str):
    """-> {comp_name: [instruction lines]}"""
    comps: Dict[str, List[str]] = {}
    cur: Optional[str] = None
    entry = None
    for line in text.splitlines():
        m = _COMP_RE.match(line.strip())
        if m and line.rstrip().endswith("{"):
            cur = m.group(1)
            comps[cur] = []
            if line.strip().startswith("ENTRY"):
                entry = cur
            continue
        if line.strip() == "}":
            cur = None
            continue
        if cur is not None:
            comps[cur].append(line)
    return comps, entry


def _instr_shapes(lines: List[str]) -> Dict[str, str]:
    """instr name -> result type string (for operand size lookup)."""
    out = {}
    for ln in lines:
        m = _INSTR_RE.match(ln)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def _trip_count(cond_lines: List[str]) -> Optional[int]:
    """Find the loop bound: the comparison constant in the condition."""
    consts = []
    for ln in cond_lines:
        for m in re.finditer(r"constant\((\d+)\)", ln):
            consts.append(int(m.group(1)))
    return max(consts) if consts else None


def _references(lines: List[str]) -> List[Tuple[str, List[str], Optional[str]]]:
    """(opcode, referenced computations, cond_name) per call-like instr."""
    refs = []
    for ln in lines:
        m = _INSTR_RE.match(ln)
        if not m:
            continue
        op = m.group(3)
        rest = m.group(4)
        if op == "while":
            body = re.search(r"body=%?([\w\.\-]+)", rest)
            cond = re.search(r"condition=%?([\w\.\-]+)", rest)
            if body:
                refs.append(("while", [body.group(1)],
                             cond.group(1) if cond else None))
        elif op == "conditional":
            bs = re.search(r"branch_computations=\{([^}]*)\}", rest)
            if bs:
                names = [s.strip().lstrip("%") for s in bs.group(1).split(",")]
                refs.append(("conditional", names, None))
            else:
                tb = re.search(r"true_computation=%?([\w\.\-]+)", rest)
                fb = re.search(r"false_computation=%?([\w\.\-]+)", rest)
                names = [x.group(1) for x in (tb, fb) if x]
                if names:
                    refs.append(("conditional", names, None))
        elif op in ("call", "fusion"):
            c = re.search(r"(?:to_apply|calls)=%?([\w\.\-]+)", rest)
            if c:
                refs.append((op, [c.group(1)], None))
    return refs


def collective_bytes(text: str,
                     default_trip: int = 1) -> CollectiveStats:
    comps, entry = _parse_computations(text)
    if entry is None:
        entry = next(iter(comps), None)
    # multipliers via BFS over the call graph
    mult: Dict[str, float] = {entry: 1.0} if entry else {}
    unresolved = 0
    frontier = [entry] if entry else []
    seen = set(frontier)
    while frontier:
        nxt = []
        for comp in frontier:
            m = mult.get(comp, 1.0)
            for op, names, cond in _references(comps.get(comp, [])):
                child_mult = m
                if op == "while":
                    trip = None
                    if cond and cond in comps:
                        trip = _trip_count(comps[cond])
                    if trip is None:
                        trip = default_trip
                        unresolved += 1
                    child_mult = m * trip
                for name in names:
                    if name in comps:
                        mult[name] = max(mult.get(name, 0.0), child_mult)
                        if name not in seen:
                            seen.add(name)
                            nxt.append(name)
        frontier = nxt

    bytes_by: Dict[str, float] = {k: 0.0 for k in _COLLECTIVES}
    count_by: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for comp, lines in comps.items():
        m = mult.get(comp, 1.0)
        shapes = _instr_shapes(lines)
        for ln in lines:
            im = _INSTR_RE.match(ln)
            if not im:
                continue
            op = im.group(3)
            kind = next((c for c in _COLLECTIVES
                         if op == c or op == c + "-start"), None)
            if kind is None:
                continue
            # operand sizes: resolve named operands from the symbol table
            opnds = re.findall(r"%([\w\.\-]+)", im.group(4).split(")")[0])
            b = sum(shape_bytes(shapes.get(o, "")) for o in opnds)
            if b == 0:  # fallback: result size
                b = shape_bytes(im.group(2))
            bytes_by[kind] += b * m
            count_by[kind] += 1
    total = sum(bytes_by.values())
    return CollectiveStats(bytes_by, count_by, total, unresolved)


@dataclasses.dataclass
class Roofline:
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    flops_total: float
    model_flops: float
    useful_ratio: float
    hbm_bytes: float
    collective_bytes_per_device: float
    chips: int
    raw_cost_flops: Optional[float] = None
    raw_cost_bytes: Optional[float] = None

    def as_dict(self):
        return dataclasses.asdict(self)


def roofline_terms(flops_total: float, model_flops: float, hbm_bytes: float,
                   coll_bytes_per_device: float, chips: int,
                   raw_cost: Optional[Dict] = None,
                   spec: ChipSpec = TPU_V5E) -> Roofline:
    compute_s = flops_total / (chips * spec.peak_flops)
    memory_s = hbm_bytes / (chips * spec.hbm_bw)
    collective_s = coll_bytes_per_device / spec.link_bw
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)
    return Roofline(
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        dominant=dominant, flops_total=flops_total, model_flops=model_flops,
        useful_ratio=model_flops / max(flops_total, 1.0),
        hbm_bytes=hbm_bytes, collective_bytes_per_device=coll_bytes_per_device,
        chips=chips,
        raw_cost_flops=(raw_cost or {}).get("flops"),
        raw_cost_bytes=(raw_cost or {}).get("bytes accessed"))


@dataclasses.dataclass
class MeasuredRoofline:
    """One live measurement against a chip spec's roofline.

    ``achieved_fraction = max(compute_s, memory_s, collective_s) / time_s``
    -- the fraction of the roofline bound actually reached (1.0 = the run
    is AT the bound for its dominant resource; > 1 means the byte/flop
    model undercounts, e.g. cache-resident traffic)."""
    time_s: float
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    achieved_fraction: float
    achieved_flops: float
    achieved_bw: float
    spec: str
    chips: int

    def as_dict(self):
        return dataclasses.asdict(self)


def measured_roofline(time_s: float, flops: float, bytes_moved: float,
                      spec: ChipSpec, chips: int = 1,
                      coll_bytes_per_device: float = 0.0) -> MeasuredRoofline:
    """Roofline placement of a measured run: modeled flops/bytes of the
    program that ran, observed wall seconds, the chip's published peaks."""
    compute_s = flops / (chips * spec.peak_flops)
    memory_s = bytes_moved / (chips * spec.hbm_bw)
    collective_s = coll_bytes_per_device / spec.link_bw
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)
    t = max(float(time_s), 1e-12)
    return MeasuredRoofline(
        time_s=float(time_s), compute_s=compute_s, memory_s=memory_s,
        collective_s=collective_s, dominant=dominant,
        achieved_fraction=max(compute_s, memory_s, collective_s) / t,
        achieved_flops=flops / t, achieved_bw=bytes_moved / t,
        spec=spec.name, chips=chips)


def roofline_summary(spec: Optional[ChipSpec], time_s: float, flops: float,
                     bytes_moved: float, chips: int = 1,
                     coll_bytes_per_device: float = 0.0) -> dict:
    """``{fraction, dominant, achieved_bw}`` of a measured run on ``spec``
    (``device_chip_spec()``), or ``{"fraction": "not measured"}`` when the
    run had no chip (``spec`` None)."""
    if spec is None:
        return dict(fraction=NOT_MEASURED)
    mr = measured_roofline(time_s, flops, bytes_moved, spec, chips=chips,
                           coll_bytes_per_device=coll_bytes_per_device)
    return dict(fraction=mr.achieved_fraction, dominant=mr.dominant,
                achieved_bw=mr.achieved_bw)
