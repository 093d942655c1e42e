"""Roofline tooling for the clustering engine's compiled programs.

Three pieces, all against the peaks of one chip kind (:data:`PEAKS`):

* :func:`chip_peaks` — the published bf16 FLOP/s, HBM bandwidth and ICI
  bandwidth of a ``jax.Device.device_kind`` (the dry runs target a TPU
  v5e, :data:`DRYRUN_KIND`).
* :func:`collective_stats` — per-kind collective bytes and counts of a
  post-partitioning HLO module. XLA's ``compiled.cost_analysis()`` counts
  a while-loop body once and does not expose collective traffic at all,
  so the walk multiplies each collective's result-shape bytes by its
  participants and by the trip counts of the loops enclosing it.
* :func:`ell_kernel_roofline` — analytic FLOP/byte models of the two
  batched ELL kernels (``neighbor_min``, ``label_agree``), a hardware
  lower bound for measured kernel walls.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks of one accelerator kind."""

    bf16_flops: float           # FLOP/s
    hbm_bytes: float            # B
    hbm_bw: float               # B/s
    ici_bw: float               # B/s per link
    source: str


# Keyed by ``jax.Device.device_kind``. A kind that is not here has no
# roofline: :func:`chip_peaks` raises rather than borrow another chip's.
PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(
        bf16_flops=197e12, hbm_bytes=16e9, hbm_bw=819e9,
        # 1,600 Gbit/s of inter-chip interconnect over 4 links.
        ici_bw=50e9,
        source='Google Cloud documentation, "TPU v5e"'),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of one chip kind (``jax.devices()[0].device_kind``)."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None


# The dry-run tools compile for a described v5e mesh.
DRYRUN_KIND = "TPU v5 lite"
PEAK_FLOPS_BF16 = PEAKS[DRYRUN_KIND].bf16_flops
HBM_BW = PEAKS[DRYRUN_KIND].hbm_bw
ICI_BW = PEAKS[DRYRUN_KIND].ici_bw

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(?[^=]+?)\s+"
                    r"([\w\-]+)\(")
_BODY_RE = re.compile(r"body=%?([\w.\-]+)")
_CALLS_RE = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")
_BRANCH_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_TRIP_RE = re.compile(r"\"known_trip_count\":\{\"n\":\"(\d+)\"")
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\([^)]*\)\s*->")


def _shape_bytes(type_str: str) -> int:
    """Total bytes of all array shapes appearing in a type string
    (handles tuples)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d.strip():
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class CollectiveStats:
    bytes_by_kind: Dict[str, float]
    count_by_kind: Dict[str, int]

    @property
    def total_bytes(self) -> float:
        return sum(self.bytes_by_kind.values())


def _split_computations(hlo: str):
    """computation name -> its body lines; plus the ENTRY name."""
    comps = {}
    cur = None
    entry = None
    for line in hlo.splitlines():
        if ((line.startswith("%") or line.startswith("ENTRY"))
                and line.rstrip().endswith("{") and "->" in line):
            head = line.split()[1] if line.startswith("ENTRY") else (
                line.split()[0])
            cur = head.lstrip("%").rstrip("(")
            comps[cur] = []
            if line.startswith("ENTRY"):
                entry = cur
            continue
        if line.strip() == "}":
            cur = None
            continue
        if cur is not None:
            comps[cur].append(line)
    return comps, entry


def _participants(line: str, default: int) -> int:
    """Group size from replica_groups (iota `[G,P]<=[...]` or legacy
    `{{...},{...}}` format)."""
    rg = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
    if rg:
        return int(rg.group(2))
    rg = re.search(r"replica_groups=\{\{([^}]*)\}", line)
    if rg:
        return len(rg.group(1).split(","))
    stp = re.search(r"source_target_pairs=\{\{(.*)\}\}", line)
    if stp:
        return stp.group(1).count("{") + 1
    return default


_COLL_RE = re.compile(
    r"(?<![%\w.\-])(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(-start|-done)?\(")
_WHILE_RE = re.compile(r"(?<![%\w.\-])while\(")
_CALLLIKE_RE = re.compile(r"(?<![%\w.\-])(call|fusion|conditional)\(")


def _result_type(line: str) -> str:
    """Text between '= ' and the op call — the result type."""
    try:
        rhs = line.split(" = ", 1)[1]
    except IndexError:
        return ""
    m = _COLL_RE.search(rhs) or _WHILE_RE.search(rhs) or _CALLLIKE_RE.search(rhs)
    return rhs[: m.start()] if m else rhs


def collective_stats(hlo: str, default_participants: int = 1
                     ) -> CollectiveStats:
    """Walk the HLO from the entry computation, multiplying collective bytes
    by enclosing while-loop trip counts (``known_trip_count`` from XLA's
    backend_config — scans always carry it).

    Bytes per op = result-shape bytes x participants (global traffic) x
    loop multiplier. Async collectives are counted at their ``-start`` op
    (which carries replica_groups); a start's result is an (in, out) buffer
    tuple, so the max element is used as the wire size.
    """
    comps, entry = _split_computations(hlo)
    bytes_by = {k: 0.0 for k in _COLLECTIVES}
    count_by = {k: 0 for k in _COLLECTIVES}
    if entry is None:
        return CollectiveStats(bytes_by, count_by)

    seen_stack = set()

    def walk(comp: str, mult: float):
        if comp not in comps or comp in seen_stack:
            return
        seen_stack.add(comp)
        for line in comps[comp]:
            if " = " not in line:
                continue
            mcoll = _COLL_RE.search(line)
            if mcoll:
                kind, suffix = mcoll.group(1), mcoll.group(2)
                if suffix == "-done":
                    continue
                type_str = _result_type(line)
                if suffix == "-start":
                    shapes = [_shape_bytes(f"{dt}[{dims}]")
                              for dt, dims in _SHAPE_RE.findall(type_str)]
                    b = max(shapes) if shapes else 0
                else:
                    b = _shape_bytes(type_str)
                parts = _participants(line, default_participants)
                bytes_by[kind] += b * parts * mult
                count_by[kind] += max(1, int(mult))
                continue
            if _WHILE_RE.search(line):
                body = _BODY_RE.search(line)
                trip_m = _TRIP_RE.search(line)
                trip = int(trip_m.group(1)) if trip_m else 1
                if body:
                    walk(body.group(1), mult * trip)
                continue
            mcall = _CALLLIKE_RE.search(line)
            if mcall:
                if mcall.group(1) == "conditional":
                    br = _BRANCH_RE.search(line)
                    if br:
                        for c in br.group(1).split(","):
                            walk(c.strip().lstrip("%"), mult)
                else:
                    c = _CALLS_RE.search(line)
                    if c:
                        walk(c.group(1), mult)
        seen_stack.discard(comp)

    walk(entry, 1.0)
    return CollectiveStats(bytes_by, count_by)


# --- Batched ELL kernel models (the clustering engine's hot loop) ----------
#
# The two Pallas kernels the fused bucket program spends its rounds in
# (repro.kernels.neighbor_min): one invocation sweeps a (B, R, W) int32 ELL
# adjacency. These analytic models give the autotuner's perf tests a
# hardware lower bound to assert measured walls against — a wall below the
# model bound means the measurement (or the model) is broken.

ELL_KERNELS = ("neighbor_min", "label_agree")


def ell_kernel_flops(kernel: str, b: int, r: int, w: int) -> float:
    """Element-op count of one batched ELL kernel invocation.

    Per (entry, row, col): ``neighbor_min`` does a rank gather, an activity
    gather, a select and a running min (≈4 ops); ``label_agree`` does a
    label gather, a compare and an accumulate (≈3 ops). Element ops, not
    MXU FLOPs — these kernels are VPU/gather bound by construction.
    """
    if kernel not in ELL_KERNELS:
        raise ValueError(f"unknown ELL kernel {kernel!r}; "
                         f"expected one of {ELL_KERNELS}")
    per_elem = 4.0 if kernel == "neighbor_min" else 3.0
    return per_elem * b * r * w


def ell_kernel_bytes(kernel: str, b: int, r: int, w: int) -> float:
    """Lower bound on HBM traffic of one batched ELL kernel invocation.

    int32 throughout: the (B, R, W) ELL read once; one gathered word per
    ELL entry per gathered table (``neighbor_min`` gathers ranks and
    activity, ``label_agree`` gathers labels); the (B, R+1) state vectors
    read once; the (B, R) output written once. A lower bound — gathers
    that miss cache cost full lines, so real traffic is ≥ this.
    """
    if kernel not in ELL_KERNELS:
        raise ValueError(f"unknown ELL kernel {kernel!r}; "
                         f"expected one of {ELL_KERNELS}")
    n_tables = 2 if kernel == "neighbor_min" else 1
    ell_words = b * r * w
    gather_words = n_tables * ell_words
    state_words = n_tables * b * (r + 1)
    out_words = b * r
    return 4.0 * (ell_words + gather_words + state_words + out_words)


@dataclasses.dataclass(frozen=True)
class KernelRoofline:
    """Roofline model of one batched ELL kernel invocation (no
    collectives — batch entries are independent)."""

    kernel: str
    b: int
    r: int
    w: int
    flops: float
    bytes_hbm: float
    peak_flops: float
    mem_bw: float

    @property
    def t_compute(self) -> float:
        return self.flops / self.peak_flops

    @property
    def t_memory(self) -> float:
        return self.bytes_hbm / self.mem_bw

    @property
    def t_model(self) -> float:
        """The model's lower bound on the invocation wall (seconds)."""
        return max(self.t_compute, self.t_memory)

    @property
    def bottleneck(self) -> str:
        return "compute" if self.t_compute >= self.t_memory else "memory"

    def as_dict(self) -> dict:
        return {
            "kernel": self.kernel,
            "shape": [self.b, self.r, self.w],
            "flops": self.flops,
            "bytes_hbm": self.bytes_hbm,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_model_s": self.t_model,
            "bottleneck": self.bottleneck,
        }


def ell_kernel_roofline(kernel: str, b: int, r: int, w: int, *,
                        device_kind: Optional[str] = None,
                        peak_flops: Optional[float] = None,
                        mem_bw: Optional[float] = None) -> KernelRoofline:
    """Roofline bound for one ``(B, R, W)`` batched ELL kernel invocation.

    The peaks come from ``device_kind`` (:data:`PEAKS`; an unknown kind
    raises) or from explicit ``peak_flops`` and ``mem_bw``. The compute
    term uses the bf16 peak, so for these integer element-op kernels it is
    a loose lower bound.
    """
    if device_kind is not None:
        if peak_flops is not None or mem_bw is not None:
            raise ValueError("pass device_kind or explicit peaks, not both")
        peaks = chip_peaks(device_kind)
        peak_flops, mem_bw = peaks.bf16_flops, peaks.hbm_bw
    elif peak_flops is None or mem_bw is None:
        raise ValueError("pass device_kind, or both peak_flops and mem_bw")
    return KernelRoofline(kernel=kernel, b=int(b), r=int(r), w=int(w),
                          flops=ell_kernel_flops(kernel, b, r, w),
                          bytes_hbm=ell_kernel_bytes(kernel, b, r, w),
                          peak_flops=peak_flops, mem_bw=mem_bw)


__all__ = [
    "ChipPeaks", "PEAKS", "chip_peaks", "DRYRUN_KIND",
    "PEAK_FLOPS_BF16", "HBM_BW", "ICI_BW",
    "collective_stats", "CollectiveStats",
    "ELL_KERNELS", "ell_kernel_flops", "ell_kernel_bytes",
    "KernelRoofline", "ell_kernel_roofline",
]
