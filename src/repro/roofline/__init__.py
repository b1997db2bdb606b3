"""Three-term roofline analysis from compiled XLA artifacts, and the
device peaks table.

Every dry-run cell derives, from the compiled SPMD module (which is the
per-device program), a modelled lower bound — not a measured time:

    compute term     = HLO_FLOPs_per_device / peak_FLOP/s
    memory term      = HLO_bytes_per_device / HBM_bw
    collective term  = collective_wire_bytes_per_device / ICI_bw

(The prompt's "HLO_FLOPs / (chips x peak)" with module-total FLOPs equals
our "per-device / peak" — XLA's cost_analysis on the partitioned module
already reports per-device numbers.)

``collective_bytes`` is not in cost_analysis: ``parse_collectives`` scans
the optimized HLO text, sums operand/result sizes of every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute, and converts
to wire bytes with the standard ring-algorithm factors:

    all-gather        (n-1)/n * gathered_bytes
    reduce-scatter    (n-1)   * scattered_bytes    (== (n-1)/n * input)
    all-reduce        2 (n-1)/n * payload_bytes    (ring RS + AG)
    all-to-all        (n-1)/n * payload_bytes
    collective-permute  payload_bytes

The peaks come from ``DEVICE_PEAKS``, keyed by ``device_kind``
(``device_peaks``).  A run on a TPU prices against its own chip and fails
on a kind the table lacks; a run on any other platform prices against
``TARGET_PEAKS``, the v5e, as a modelling target.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field


@dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks of one accelerator kind."""

    flops: float  # bf16 FLOP/s
    hbm_bw: float  # HBM bytes/s
    ici_bw: float  # bytes/s of one ICI link: the conservative per-chip
    # injection rate the collective term uses
    hbm_bytes: float  # HBM capacity
    source: str


DEVICE_PEAKS: dict[str, DevicePeaks] = {
    "TPU v5 lite": DevicePeaks(
        flops=197e12, hbm_bw=819e9, ici_bw=50e9, hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
        "16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI per chip over 4 links",
    ),
}

# The chip that runs on no TPU price against: a modelling target, not the
# device they run on.
TARGET_PEAKS = DEVICE_PEAKS["TPU v5 lite"]


def device_peaks(device=None) -> DevicePeaks:
    """Peaks of ``device`` (default: ``jax.devices()[0]``).

    A TPU is looked up by its ``device_kind`` and an unknown kind raises
    ``KeyError``; any other platform gets ``TARGET_PEAKS``.
    """
    if device is None:
        import jax

        device = jax.devices()[0]
    if device.platform != "tpu":
        return TARGET_PEAKS
    try:
        return DEVICE_PEAKS[device.device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for TPU kind {device.device_kind!r}; add "
            f"it to repro.roofline.DEVICE_PEAKS with its source"
        ) from None


_DTYPE_BYTES = {
    "pred": 1,
    "s4": 1, "u4": 1,
    "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e3m4": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
    "token": 0,
    "s2": 1, "u2": 1,
}

_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

# `%name = SHAPE op(` where SHAPE is `bf16[1,2,3]{...}` or a (tuple, of, them)
_INSTR_RE = re.compile(
    r"=\s*(\([^=]*?\)|[a-z0-9]+\[[^\]]*\](?:\{[^}]*\})?)\s+"
    r"(all-gather-start|all-gather|all-reduce-start|all-reduce|"
    r"reduce-scatter|all-to-all|collective-permute-start|collective-permute)\("
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9,]*)\}")


def shape_bytes(shape_text: str) -> int:
    """Total bytes of one HLO shape string (tuples summed)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        ids = m.group(1)
        return len(ids.split(",")) if ids else 1
    return default


@dataclass
class CollectiveStats:
    """Per-device collective traffic of one compiled module."""

    by_kind_bytes: dict[str, float] = field(default_factory=dict)
    by_kind_count: dict[str, int] = field(default_factory=dict)
    wire_bytes: float = 0.0  # ring-model bytes on the wire, per device
    payload_bytes: float = 0.0  # raw summed result sizes

    def add(self, kind: str, payload: int, wire: float) -> None:
        self.by_kind_bytes[kind] = self.by_kind_bytes.get(kind, 0.0) + wire
        self.by_kind_count[kind] = self.by_kind_count.get(kind, 0) + 1
        self.wire_bytes += wire
        self.payload_bytes += payload


def parse_collectives(hlo_text: str, *, default_group: int = 1) -> CollectiveStats:
    """Sum collective traffic from optimized HLO text (one device's module)."""
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        m = _INSTR_RE.search(line)
        if m is None:
            continue
        shape_text, op = m.group(1), m.group(2)
        kind = op.removesuffix("-start")
        payload = shape_bytes(shape_text)
        n = max(_group_size(line, default_group), 1)
        if kind == "all-gather":
            # result shape is the gathered (full) buffer
            wire = payload * (n - 1) / n
        elif kind == "reduce-scatter":
            # result shape is the scattered (1/n) buffer; input = n * result
            wire = payload * (n - 1)
        elif kind == "all-reduce":
            wire = 2.0 * payload * (n - 1) / n
        elif kind == "all-to-all":
            wire = payload * (n - 1) / n
        else:  # collective-permute
            wire = float(payload)
        stats.add(kind, payload, wire)
    return stats


# ---------------------------------------------------------------------------
# roofline report
# ---------------------------------------------------------------------------


@dataclass
class RooflineReport:
    flops_per_device: float
    hbm_bytes_per_device: float
    collective_bytes_per_device: float
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops_total: float
    useful_flops_ratio: float  # MODEL_FLOPS / (per-device HLO flops * chips)
    collectives: CollectiveStats
    memory: dict[str, float]
    top_collectives: list = field(default_factory=list)
    top_memory: list = field(default_factory=list)
    top_flops: list = field(default_factory=list)
    # memory term with attention-prob tile traffic replaced by the Pallas
    # flash kernel's true HBM streaming (the TPU perf path)
    memory_s_kernel: float = 0.0
    attn_tile_bytes: float = 0.0

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the step spent on the compute roof if terms overlap
        perfectly: compute_s / max(all terms) — 1.0 means compute-bound at
        the roof."""
        b = self.bound_s
        return self.compute_s / b if b > 0 else 0.0

    def to_json(self) -> dict:
        return {
            "flops_per_device": self.flops_per_device,
            "hbm_bytes_per_device": self.hbm_bytes_per_device,
            "collective_bytes_per_device": self.collective_bytes_per_device,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "model_flops_total": self.model_flops_total,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
            "collective_by_kind_bytes": self.collectives.by_kind_bytes,
            "collective_by_kind_count": self.collectives.by_kind_count,
            "memory": self.memory,
            "top_collectives": [[b, d] for b, d in self.top_collectives[:8]],
            "top_memory": [[b, d] for b, d in self.top_memory[:8]],
            "top_flops": [[b, d] for b, d in self.top_flops[:8]],
            "memory_s_kernel": self.memory_s_kernel,
            "attn_tile_bytes": self.attn_tile_bytes,
        }


def analyze(
    compiled,
    *,
    n_chips: int,
    model_flops_total: float,
    peak_flops: float | None = None,
    hbm_bw: float | None = None,
    ici_bw: float | None = None,
    attn_tile_signature: tuple[int, int] | None = (512, 1024),
    flash_kernel_bytes: float = 0.0,
) -> RooflineReport:
    """Roofline terms from one compiled (SPMD-partitioned) executable.

    Uses the trip-count-aware HLO cost model (``hlo_cost.analyze_hlo``):
    XLA's aggregate cost_analysis() counts every while body once, which
    under-counts scanned-layer programs by the layer count (verified in
    tests/test_roofline.py), so it is only kept as a cross-check floor.

    Kernel adjustment: the dry-run lowers the pure-jnp chunked attention
    (the CPU oracle), which streams (q_chunk x kv_chunk) f32 probability
    tiles through HBM.  The TPU perf path is the Pallas flash kernel
    (kernels/flash_attention.py) where those tiles live in VMEM.  The
    report therefore carries BOTH memory terms: raw HLO, and
    kernel-adjusted = raw - measured tile traffic + ``flash_kernel_bytes``
    (the kernel's true Q/K/V/O streaming, computed analytically by the
    caller).  EXPERIMENTS.md §Roofline reports both.

    Peaks left None come from ``device_peaks()``.
    """
    from repro.roofline.hlo_cost import analyze_hlo

    peaks = device_peaks()
    peak_flops = peak_flops or peaks.flops
    hbm_bw = hbm_bw or peaks.hbm_bw
    ici_bw = ici_bw or peaks.ici_bw

    hlo = compiled.as_text()
    cost = analyze_hlo(
        hlo, default_group=n_chips, attn_tile_signature=attn_tile_signature
    )
    flops = cost.flops
    hbm_bytes = cost.hbm_bytes

    stats = CollectiveStats(
        by_kind_bytes=dict(cost.by_kind_bytes),
        by_kind_count={k: int(v) for k, v in cost.by_kind_count.items()},
        wire_bytes=cost.collective_wire_bytes,
        payload_bytes=cost.collective_payload_bytes,
    )

    mem = compiled.memory_analysis()
    memory = {
        "argument_bytes": float(getattr(mem, "argument_size_in_bytes", 0)),
        "output_bytes": float(getattr(mem, "output_size_in_bytes", 0)),
        "temp_bytes": float(getattr(mem, "temp_size_in_bytes", 0)),
        "alias_bytes": float(getattr(mem, "alias_size_in_bytes", 0)),
        "peak_bytes": float(
            getattr(mem, "argument_size_in_bytes", 0)
            + getattr(mem, "output_size_in_bytes", 0)
            + getattr(mem, "temp_size_in_bytes", 0)
            - getattr(mem, "alias_size_in_bytes", 0)
        ),
    }

    compute_s = flops / peak_flops
    memory_s = hbm_bytes / hbm_bw
    collective_s = stats.wire_bytes / ici_bw
    adj_bytes = max(hbm_bytes - cost.attn_tile_bytes + flash_kernel_bytes, 0.0)
    memory_s_kernel = adj_bytes / hbm_bw
    terms = {
        "compute": compute_s,
        "memory": memory_s,
        "collective": collective_s,
    }
    dominant = max(terms, key=terms.get)
    total_hlo_flops = flops * n_chips
    useful = model_flops_total / total_hlo_flops if total_hlo_flops else 0.0
    return RooflineReport(
        flops_per_device=flops,
        hbm_bytes_per_device=hbm_bytes,
        collective_bytes_per_device=stats.wire_bytes,
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=collective_s,
        dominant=dominant,
        model_flops_total=model_flops_total,
        useful_flops_ratio=useful,
        collectives=stats,
        memory=memory,
        top_collectives=cost.top_collectives,
        top_memory=cost.top_memory,
        top_flops=cost.top_flops,
        memory_s_kernel=memory_s_kernel,
        attn_tile_bytes=cost.attn_tile_bytes,
    )


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS: 6 N D (train), 2 N D (prefill), 2 N_active B (decode)."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n_active * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.global_batch * shape.seq_len
    return 2.0 * n_active * shape.global_batch  # decode: 1 token / sequence


# local SpGEMM stage flop models (surviving-product accounting) — the
# predicted side of the measured-vs-modeled assertions in test_roofline
from repro.roofline.hlo_cost import (  # noqa: E402
    spgemm_dense_flops,
    spgemm_stacks_flops,
)
