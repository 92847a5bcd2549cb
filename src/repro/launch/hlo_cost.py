"""Scan-correct cost extraction from compiled (post-SPMD) HLO text.

``compiled.cost_analysis()`` counts while-loop bodies ONCE (XLA cannot assume
trip counts), which under-counts scan-over-layers / gradient-accumulation
programs by orders of magnitude.  This parser walks the HLO call graph and
multiplies each while body by its ``known_trip_count`` backend_config, giving
per-device totals for:

  * flops            — dot/convolution ops (2 * result_elems * contracted)
  * hbm_bytes        — operand + result bytes of dot / fusion / copy /
                       collective ops (a one-pass-over-operands HBM model;
                       VMEM-resident reuse inside a fusion is not charged)
  * collective_bytes — result bytes of all-reduce / all-gather /
                       reduce-scatter / all-to-all / collective-permute
                       (per-kind breakdown included)

Shapes in post-partitioning HLO are per-device, so all totals are per-device.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s*([\w\-]+)\(")
_CALLED_RE = re.compile(r"(?:body|to_apply|calls|branch_computations|called_computations)=\{?%?([\w.\-]+(?:,\s*%?[\w.\-]+)*)\}?")
_COND_RE = re.compile(r"condition=%?([\w.\-]+)")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _shape_elems(type_str: str) -> int:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return 0
    dims = m.group(2)
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n


def _result_dims(type_str: str) -> list[int]:
    m = _SHAPE_RE.search(type_str)
    if not m or not m.group(2):
        return []
    return [int(d) for d in m.group(2).split(",")]


@dataclasses.dataclass
class Cost:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    collective_bytes: float = 0.0
    by_collective: dict = dataclasses.field(default_factory=lambda: defaultdict(float))
    n_collectives: int = 0

    def __iadd__(self, other: "Cost"):
        self.flops += other.flops
        self.hbm_bytes += other.hbm_bytes
        self.collective_bytes += other.collective_bytes
        for k, v in other.by_collective.items():
            self.by_collective[k] += v
        self.n_collectives += other.n_collectives
        return self

    def scaled(self, m: float) -> "Cost":
        return Cost(
            self.flops * m,
            self.hbm_bytes * m,
            self.collective_bytes * m,
            defaultdict(float, {k: v * m for k, v in self.by_collective.items()}),
            int(self.n_collectives * m),
        )


def _split_computations(hlo: str) -> dict[str, list[str]]:
    comps: dict[str, list[str]] = {}
    entry_name = None
    cur = None
    for line in hlo.splitlines():
        stripped = line.strip()
        if stripped.endswith("{") and not stripped.startswith("}"):
            # computation header iff the text before the first '(' has no '='
            # (op lines are '%x = type op(...)'; param lists may contain
            # '=' only inside sharding annotations AFTER the '(')
            head = stripped.split("(", 1)[0]
            if "=" not in head:
                m = re.match(r"^(ENTRY\s+)?%?([\w.\-]+)", stripped)
                if m:
                    cur = m.group(2)
                    comps[cur] = []
                    if m.group(1):
                        entry_name = cur
                    continue
        if stripped.startswith("}"):
            cur = None
            continue
        if cur is not None:
            comps[cur].append(line)
    if entry_name is not None:
        comps["__entry__"] = comps[entry_name]
    return comps


def parse_hlo_cost(hlo: str, entry_hint: str | None = None) -> Cost:
    comps = _split_computations(hlo)
    # entry: the ENTRY block, else a 'main*' computation, else the first
    entry = entry_hint
    if entry is None and "__entry__" in comps:
        entry = "__entry__"
    if entry is None:
        for name in comps:
            if name.startswith("main"):
                entry = name
                break
    if entry is None:
        entry = next(iter(comps))

    memo: dict[str, Cost] = {}

    def comp_cost(name: str) -> Cost:
        if name in memo:
            return memo[name]
        memo[name] = Cost()  # cycle guard
        total = Cost()
        lines = comps.get(name, [])
        # per-computation symbol table for operand shapes
        table: dict[str, str] = {}
        parsed = []
        for line in lines:
            dm = _DEF_RE.match(line)
            if not dm:
                continue
            vname, vtype, op = dm.group(1), dm.group(2), dm.group(3)
            table[vname] = vtype
            parsed.append((vname, vtype, op, line))
        for vname, vtype, op, line in parsed:
            if op in ("dot", "dot_general"):
                # flops = 2 * result_elems * contracted_size
                lhs_m = _OPERAND_RE.findall(line.split("(", 1)[1])
                lhs_shape = table.get(lhs_m[0], "") if lhs_m else ""
                cdims_m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", line)
                contracted = 1
                if cdims_m and lhs_shape:
                    ldims = _result_dims(lhs_shape)
                    for ci in cdims_m.group(1).split(","):
                        if ci != "" and int(ci) < len(ldims):
                            contracted *= ldims[int(ci)]
                res_elems = _shape_elems(vtype)
                total.flops += 2.0 * res_elems * contracted
                total.hbm_bytes += _shape_bytes(vtype) + sum(
                    _shape_bytes(table.get(o, "")) for o in lhs_m[:2]
                )
            elif op == "convolution":
                res_elems = _shape_elems(vtype)
                total.flops += 2.0 * res_elems * 8  # small; conv is rare here
                total.hbm_bytes += _shape_bytes(vtype)
            # 'copy' is excluded: XLA:CPU materializes while-carry aliasing
            # copies that the TPU backend elides (donated/aliased buffers);
            # charging them inflated the HBM proxy ~2x.
            elif op in ("fusion", "transpose", "reshape", "reduce",
                        "concatenate", "select-and-scatter", "sort"):
                # one pass over operands + result (real HBM traffic)
                ops_m = _OPERAND_RE.findall(line.split("(", 1)[1])
                total.hbm_bytes += _shape_bytes(vtype) + sum(
                    _shape_bytes(table.get(o, "")) for o in ops_m[:8]
                )
            elif op in ("broadcast", "iota", "pad"):
                total.hbm_bytes += _shape_bytes(vtype)  # write-only
            elif op in ("slice", "dynamic-slice", "gather"):
                total.hbm_bytes += 2 * _shape_bytes(vtype)  # read+write the slice
            elif op in ("dynamic-update-slice", "scatter"):
                # traffic ~ the update operand, not the full target buffer
                ops_m = _OPERAND_RE.findall(line.split("(", 1)[1])
                upd = _shape_bytes(table.get(ops_m[1], "")) if len(ops_m) > 1 else 0
                total.hbm_bytes += 2 * upd
            elif op in _COLLECTIVES:
                nbytes = _shape_bytes(vtype)
                total.collective_bytes += nbytes
                total.by_collective[op] += nbytes
                total.n_collectives += 1
                total.hbm_bytes += 2 * nbytes
            if op == "while":
                body_m = re.search(r"body=%?([\w.\-]+)", line)
                trip_m = _TRIP_RE.search(line)
                trips = int(trip_m.group(1)) if trip_m else 1
                if body_m:
                    total += comp_cost(body_m.group(1)).scaled(trips)
                cond_m = _COND_RE.search(line)
                if cond_m:
                    total += comp_cost(cond_m.group(1)).scaled(trips)
            elif op in ("call", "custom-call", "conditional", "async-start", "fusion"):
                for grp in _CALLED_RE.findall(line):
                    for cname in re.split(r",\s*%?", grp):
                        if cname in comps:
                            sub = comp_cost(cname)
                            if op == "fusion":
                                # operand/result bytes already charged at the
                                # call site; only dots matter inside fusions
                                sub = dataclasses.replace(
                                    sub, hbm_bytes=0.0,
                                    by_collective=defaultdict(float, sub.by_collective),
                                )
                            total += sub
        memo[name] = total
        return total

    return comp_cost(entry)


# --------------------------------------------------------------------------
# Roofline terms: published per-chip peaks, keyed by device kind
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    flops: float  # bf16 FLOP/s per chip
    hbm_bw: float  # HBM bytes/s per chip
    ici_bw: float  # interconnect bytes/s per link


#: Published peaks per ``jax.Device.device_kind``.  "TPU v5 lite" is the
#: TPU v5e (Google Cloud documentation, "TPU v5e"): 197 TFLOP/s bf16,
#: 819 GB/s HBM, 1,600 Gbit/s of interconnect per chip over 4 links.
DEVICE_PEAKS: dict[str, DevicePeaks] = {
    "TPU v5 lite": DevicePeaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}

#: The chip the cost model plans for on a host without a TPU (CPU tests,
#: dry runs): the deployment target, not the host.
REFERENCE_KIND = "TPU v5 lite"


def peaks_for(kind: str) -> DevicePeaks:
    """Peaks of one device kind; a kind missing from the table is an error,
    never a silent v5e."""
    try:
        return DEVICE_PEAKS[kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {kind!r}; add it to "
            f"repro.launch.hlo_cost.DEVICE_PEAKS (known: {sorted(DEVICE_PEAKS)})"
        ) from None


def device_peaks() -> DevicePeaks:
    """Peaks of the attached TPU, or of ``REFERENCE_KIND`` when the host has
    no TPU."""
    import jax

    dev = jax.devices()[0]
    return peaks_for(dev.device_kind if dev.platform == "tpu" else REFERENCE_KIND)


def roofline_terms(cost: Cost) -> dict:
    """Seconds per term, per chip (cost is already per-device)."""
    peaks = device_peaks()
    t_compute = cost.flops / peaks.flops
    t_memory = cost.hbm_bytes / peaks.hbm_bw
    t_collective = cost.collective_bytes / peaks.ici_bw
    dominant = max(
        ("compute", t_compute), ("memory", t_memory), ("collective", t_collective),
        key=lambda kv: kv[1],
    )[0]
    return {
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_collective,
        "dominant": dominant,
        "flops_per_device": cost.flops,
        "hbm_bytes_per_device": cost.hbm_bytes,
        "collective_bytes_per_device": cost.collective_bytes,
        "collective_breakdown": dict(cost.by_collective),
    }
