"""Hardware constants for roofline analysis and the paper's cost model.

Two parameter sets coexist:
  * ``TPU_V5E``  — the executable-reproduction target (roofline terms),
    reachable by the ``device_kind`` JAX reports through
    :func:`chip_for_kind`.
  * ``PAPER_28NM`` — the paper's 28nm CMOS evaluation context, used by
    ``core.cost_model`` to reproduce the paper's figures.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """One accelerator chip, as seen by the roofline model."""

    name: str
    peak_bf16_flops: float   # FLOP/s
    hbm_bandwidth: float     # bytes/s
    ici_link_bandwidth: float  # bytes/s per link
    ici_links: int           # links per chip (2D torus: 4)
    hbm_bytes: int           # capacity
    vmem_bytes: int          # usable VMEM per core
    clock_hz: float

    @property
    def flops_per_byte_balance(self) -> float:
        return self.peak_bf16_flops / self.hbm_bandwidth


TPU_V5E = ChipSpec(
    name="tpu_v5e",
    peak_bf16_flops=197e12,
    hbm_bandwidth=819e9,
    ici_link_bandwidth=50e9,
    ici_links=4,
    hbm_bytes=16 * 1024**3,
    vmem_bytes=64 * 1024**2,
    clock_hz=0.94e9,
)

# TPU chips by the ``device_kind`` string JAX reports for them.  Peaks of
# TPU_V5E: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
# 16 GB HBM at 819 GB/s).
TPU_CHIPS: dict[str, ChipSpec] = {
    "TPU v5 lite": TPU_V5E,
}


def chip_for_kind(device_kind: str) -> ChipSpec:
    """Peak table entry for a TPU ``device_kind``; an unknown kind is an
    error, never a default."""
    try:
        return TPU_CHIPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak table entry for TPU device kind {device_kind!r}; "
            f"known: {sorted(TPU_CHIPS)} (add it to TPU_CHIPS with its "
            f"source)") from None


# VPU throughput estimate used by the decompression napkin math in DESIGN.md:
# 8 sublanes x 128 lanes x ~2 ALU ops / cycle.
TPU_V5E_VPU_OPS_PER_CYCLE = 2048.0


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Logical mesh used for the roofline collective term."""

    axes: tuple[str, ...]
    shape: tuple[int, ...]
    chip: ChipSpec = TPU_V5E

    @property
    def num_chips(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n

    def axis_size(self, name: str) -> int:
        return self.shape[self.axes.index(name)]


SINGLE_POD = MeshSpec(axes=("data", "model"), shape=(16, 16))
MULTI_POD = MeshSpec(axes=("pod", "data", "model"), shape=(2, 16, 16))


# ---------------------------------------------------------------------------
# Paper's 28 nm evaluation context (Section IV).  Energy numbers are standard
# 28/45 nm scaling values (Horowitz ISSCC'14 style) that reproduce the
# qualitative and quantitative behaviour reported in the paper: DRAM access
# dominates, SRAM ~1-2 orders below, MAC lowest.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class PaperTech:
    name: str = "28nm"
    clock_hz: float = 500e6
    # energy per element-access / op (pJ), 16-bit datapath
    e_dram_per_bit: float = 20.0      # pJ/bit off-chip DRAM
    e_sram_per_bit: float = 0.35      # pJ/bit large global SRAM buffer
    e_mac_16b: float = 1.0            # pJ per 16-bit MAC (mult+add+reg)
    e_index_match: float = 0.25       # pJ per index comparison (sparse PEs)
    e_fifo_per_bit: float = 0.10      # pJ/bit FIFO traversal
    # area, mm^2 (28nm; calibrated so the dense baseline reproduces the
    # paper's Table II absolute TOPS/mm²: 0.956 logic-only, 0.430 +2MB SRAM)
    a_dense_pe: float = 1.046e-3      # one 16-bit MAC PE incl. pipeline regs
    a_sram_per_kb: float = 2.56e-3    # global buffer SRAM
    # value/index bit widths used throughout the paper
    bits_value: int = 16
    bits_index: int = 8


PAPER_28NM = PaperTech()
