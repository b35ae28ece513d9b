"""Roofline analysis from compiled dry-run artifacts.

Three terms per (arch x shape x mesh) cell, all in seconds:

    compute_s    = HLO_FLOPs_per_chip / peak_FLOP/s
    memory_s     = HLO_bytes_per_chip / HBM_bw
    collective_s = ici_wire_bytes/chip / ici_bw  +  dcn_wire_bytes/chip / dcn_bw

with the peaks of the target chip looked up by its jax ``device_kind``
(:data:`PEAKS`); a kind with no published peaks is an error.

``cost_analysis()`` on the compiled (post-SPMD) module is already per-chip.
Collective bytes are NOT in cost_analysis: we parse the optimized HLO, resolve
each collective's operand/result shapes through a symbol table of the module's
definitions, convert to *wire* bytes with the standard ring factors, and classify
each op as ICI (intra-pod) or DCN (crosses the ``pod`` boundary) by evaluating
its ``replica_groups`` (including the compact iota form) against the device-id
pod boundary (256 ids per pod).

Wire bytes per chip (ring algorithms, group size g):
    all-gather       out * (g-1)/g
    reduce-scatter   in  * (g-1)/g  ==  out * (g-1)
    all-reduce       2 * in * (g-1)/g
    all-to-all       in * (g-1)/g
    collective-permute  out
"""
from __future__ import annotations

import dataclasses
import re

import numpy as np

from repro.core import topology

POD_SIZE = 256


@dataclasses.dataclass(frozen=True)
class Peaks:
    """Per-chip peaks, in FLOP/s and bytes/s."""

    flops: float
    hbm_bw: float
    ici_bw: float           # per ICI link
    dcn_bw: float           # per chip across pods


# Keyed by jax's ``device_kind``.  "TPU v5 lite" (v5e): Google Cloud TPU
# documentation, "TPU v5e" — 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s of
# ICI over 4 links (50 GB/s each).  The DCN share is the modelled 50 Gbit/s
# NIC share per chip of repro.core.topology, not a published figure.
PEAKS = {
    "TPU v5 lite": Peaks(flops=topology.TPU_PEAK_FLOPS_BF16,
                         hbm_bw=topology.TPU_HBM_BW,
                         ici_bw=topology.TPU_ICI_BW_PER_LINK,
                         dcn_bw=topology.TPU_DCN_BW_PER_CHIP),
}


def peaks_for(device_kind: str) -> Peaks:
    """The published peaks of ``device_kind``; raises for an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r} (known: {sorted(PEAKS)})") from None


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "token": 0,
}

# %name = TYPE ...   (definition lines; TYPE may be a tuple)
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+)$")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(type_str: str) -> int:
    """Total bytes of one HLO type string (sums tuple elements)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _iota_groups(expr: str) -> np.ndarray | None:
    """Evaluate ``replica_groups=[G,S]<=[dims]T(perm)`` (iota form) to [G,S] ids."""
    m = re.match(r"\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?", expr)
    if not m:
        return None
    g, s = int(m.group(1)), int(m.group(2))
    dims = [int(d) for d in m.group(3).split(",")]
    ids = np.arange(int(np.prod(dims))).reshape(dims)
    if m.group(4):
        perm = [int(p) for p in m.group(4).split(",")]
        ids = ids.transpose(perm)
    return ids.reshape(g, s)


def _explicit_groups(expr: str) -> np.ndarray | None:
    m = re.match(r"\{(.+)\}$", expr.strip())
    if not m:
        return None
    groups = re.findall(r"\{([\d,\s]+)\}", expr)
    if not groups:
        return None
    parsed = [[int(x) for x in g.replace(" ", "").split(",") if x] for g in groups]
    width = max(len(g) for g in parsed)
    return np.asarray([g + g[-1:] * (width - len(g)) for g in parsed])


def _group_info(line: str, pod_size: int) -> tuple[int, bool]:
    """(group size, crosses_pod) from the replica_groups annotation."""
    m = re.search(r"replica_groups=(\[[^\]]*\](?:<=\[[\d,]+\](?:T\([\d,]+\))?)?"
                  r"|\{\{[^=]*?\}\})", line)
    if not m:
        return 1, False
    expr = m.group(1)
    groups = _iota_groups(expr)
    if groups is None:
        groups = _explicit_groups(expr)
    if groups is None:
        return 1, False
    crosses = bool(np.any(groups // pod_size !=
                          (groups[:, :1] // pod_size)))
    return int(groups.shape[1]), crosses


@dataclasses.dataclass
class CollectiveStats:
    ici_bytes: float = 0.0        # wire bytes per chip, intra-pod collectives
    dcn_bytes: float = 0.0        # wire bytes per chip, pod-crossing collectives
    by_op: dict = dataclasses.field(default_factory=dict)
    count: int = 0


def parse_collectives(hlo_text: str, pod_size: int = POD_SIZE) -> CollectiveStats:
    # symbol table: %name -> byte size of its result type
    sizes: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if m:
            sizes[m.group(1)] = _shape_bytes(m.group(2))

    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if not m:
            continue
        rhs = m.group(2)
        opm = re.search(r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|"
                        r"collective-permute)(-start)?\(", rhs)
        if not opm:
            continue
        op = opm.group(1)
        out_bytes = _shape_bytes(rhs.split(op)[0])
        # operand bytes via the symbol table (handles multi-operand tuples)
        operands = re.findall(r"%([\w.\-]+)", rhs[opm.end():].split(")")[0])
        in_bytes = sum(sizes.get(o, 0) for o in operands) or out_bytes
        g, crosses = _group_info(line, pod_size)
        if g <= 1:
            continue
        if op == "all-gather":
            wire = out_bytes * (g - 1) / g
        elif op == "all-reduce":
            wire = 2 * in_bytes * (g - 1) / g
        elif op == "reduce-scatter":
            wire = in_bytes * (g - 1) / g
        elif op == "all-to-all":
            wire = in_bytes * (g - 1) / g
        else:                      # collective-permute
            wire = out_bytes
        stats.count += 1
        key = (op, "dcn" if crosses else "ici")
        stats.by_op[key] = stats.by_op.get(key, 0.0) + wire
        if crosses:
            stats.dcn_bytes += wire
        else:
            stats.ici_bytes += wire
    return stats


# ---------------------------------------------------------------------------
# Roofline terms
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_chip: float
    hbm_bytes_per_chip: float
    ici_bytes_per_chip: float
    dcn_bytes_per_chip: float
    model_flops: float             # 6*N*D (train) / 2*N*D (serve), global
    peaks: Peaks                   # of the target chip
    collective_count: int = 0
    per_chip_hbm_gb: float = 0.0   # argument+temp from memory_analysis
    flash_bytes_per_chip: float = 0.0  # XLA-path attention traffic the Pallas
    #                                    kernel keeps in VMEM (named-scope tagged)

    @property
    def compute_s(self) -> float:
        return self.flops_per_chip / self.peaks.flops

    @property
    def memory_s(self) -> float:
        return self.hbm_bytes_per_chip / self.peaks.hbm_bw

    @property
    def memory_s_kernel(self) -> float:
        """Memory term with the Pallas flash kernel: the tagged attention
        inner-loop traffic (logits / online-softmax state) lives in VMEM."""
        return max(0.0, self.hbm_bytes_per_chip
                   - self.flash_bytes_per_chip) / self.peaks.hbm_bw

    @property
    def collective_s(self) -> float:
        return (self.ici_bytes_per_chip / self.peaks.ici_bw
                + self.dcn_bytes_per_chip / self.peaks.dcn_bw)

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Roofline-bound step time = max of the three overlappable terms."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def model_flops_ratio(self) -> float:
        """useful (model) FLOPs / compiled HLO FLOPs — remat/redundancy waste."""
        hlo = self.flops_per_chip * self.chips
        return self.model_flops / hlo if hlo else 0.0

    @property
    def mfu(self) -> float:
        """Model-FLOPs utilization at the roofline-bound step time."""
        t = self.step_time_s
        if t <= 0:
            return 0.0
        return self.model_flops / (t * self.chips * self.peaks.flops)

    def row(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "memory_s_kernel": self.memory_s_kernel,
            "collective_s": self.collective_s,
            "ici_gb": self.ici_bytes_per_chip / 1e9,
            "dcn_gb": self.dcn_bytes_per_chip / 1e9,
            "dominant": self.dominant,
            "step_time_s": self.step_time_s,
            "model_flops_ratio": self.model_flops_ratio,
            "mfu": self.mfu,
            "hbm_gb": self.per_chip_hbm_gb,
            "collectives": self.collective_count,
        }


def model_flops_for(cfg, shape) -> float:
    """6*N_active*D for training, 2*N_active*D for inference (D = tokens/step)."""
    n = cfg.num_active_params()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    tokens = shape.global_batch            # decode: one token per sequence
    return 2.0 * n * tokens


def analyze(compiled, *, arch: str, shape, mesh, cfg,
            device_kind: str) -> Roofline:
    """Loop-aware roofline from the compiled HLO (see hlo_analysis), against
    the peaks of ``device_kind``, the chip the mesh stands for.

    ``cost_analysis()`` counts while bodies once; scans (layers, microbatches,
    attention blocks) would be under-counted by orders of magnitude, so flops /
    bytes / collectives come from the trip-count-scaled static analyzer.
    """
    from .hlo_analysis import analyze_hlo
    chips = 1
    for v in mesh.shape.values():
        chips *= v
    pod_size = chips // mesh.shape.get("pod", 1)
    cost = analyze_hlo(compiled.as_text(), pod_size=pod_size)
    hbm_gb = 0.0
    try:
        ma = compiled.memory_analysis()
        hbm_gb = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                  + ma.output_size_in_bytes - ma.alias_size_in_bytes) / 1e9
    except Exception:
        pass
    return Roofline(
        arch=arch, shape=shape.name,
        mesh="x".join(str(v) for v in mesh.shape.values()),
        chips=chips, flops_per_chip=cost.flops, hbm_bytes_per_chip=cost.hbm_bytes,
        ici_bytes_per_chip=cost.ici_bytes, dcn_bytes_per_chip=cost.dcn_bytes,
        model_flops=model_flops_for(cfg, shape),
        peaks=peaks_for(device_kind),
        collective_count=int(cost.collective_count), per_chip_hbm_gb=hbm_gb,
        flash_bytes_per_chip=cost.flash_bytes)
