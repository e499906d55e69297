"""Hardware platform descriptions for the analytical model.

Two families:

* :class:`FPGAPlatform` — the paper's target (Xilinx Alveo U280).  Used to
  run the paper-exact analytical model (Eqs. 1-9) and reproduce the paper's
  parallelism decisions / speedups (Table 3, Sec. 5.4).

* :class:`TPUPlatform` — our deployment target (TPU v5e pods).  The SASA
  latency model is re-derived against the TPU memory hierarchy:
  HBM->VMEM->VREG replaces HBM->AXI/FIFO->FF, fused-iteration Pallas tiles
  replace cascaded PE pipelines, and ICI collective-permutes replace
  on-chip border streaming wires.

All numbers are per-chip unless stated otherwise.  TPU v5e roofline
constants follow the assignment: 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class FPGAPlatform:
    """Xilinx Alveo U280 (paper Section 5.1)."""

    name: str = "xilinx-u280"
    freq_hz: float = 225e6                 # target frequency; >=225MHz saturates HBM
    hbm_banks: int = 32
    bank_bw: float = 14.4e9                # 512b/cycle @ 225MHz
    num_slrs: int = 3
    # chip resources (U280 datasheet)
    luts: int = 1_304_000
    ffs: int = 2_607_000
    brams: int = 2_016                     # BRAM36 blocks
    dsps: int = 9_024
    alpha: float = 0.75                    # Eq. 1 utilisation constraint
    reserved_banks: int = 2                # shell/host-reserved HBM banks
    axi_bits: int = 512


@dataclasses.dataclass(frozen=True)
class TPUPlatform:
    """TPU v5e chip + pod-slice fabric."""

    name: str = "tpu-v5e"
    peak_flops_bf16: float = 197e12        # MXU peak (LM roofline)
    vpu_flops_f32: float = 12.3e12         # VPU estimate; stencils are VPU work
    hbm_bw: float = 819e9                  # B/s
    hbm_bytes: int = 16 * 2**30
    vmem_bytes: int = 64 * 2**20           # usable VMEM budget per core
    ici_bw: float = 50e9                   # B/s per link per direction
    ici_latency: float = 1e-6              # per-hop collective latency
    num_chips: int = 8                     # chips available for the stencil job
    # 2D torus per pod; per-chip aggregate ICI is links * ici_bw, but the
    # stencil 1-D ring only ever uses two links (up/down neighbour).
    ici_links: int = 4

    def with_chips(self, n: int) -> "TPUPlatform":
        return dataclasses.replace(self, num_chips=n)


@dataclasses.dataclass(frozen=True)
class CPUPlatform:
    """Calibrated description of *this* host, used to validate the analytical
    model against measured wall-clock (the Fig. 9 accuracy experiment).

    ``flops`` / ``mem_bw`` are measured by :func:`calibrate` at benchmark
    time rather than hard-coded.
    """

    name: str = "host-cpu"
    flops: float = 5.0e10
    mem_bw: float = 2.0e10
    vmem_bytes: int = 1 * 2**20            # L2-ish tile budget; only used for tiling
    num_chips: int = 1
    ici_bw: float = 1.0e10                 # shard_map on host devices: shared memcpy
    ici_latency: float = 5e-6


DEFAULT_FPGA = FPGAPlatform()

#: Chip descriptions keyed by ``jax.Device.device_kind``.  A TPU whose kind
#: is missing here is an error (:func:`platform_for`), never a default.
TPU_PLATFORMS = {
    "TPU v5 lite": TPUPlatform(name="tpu-v5e"),
}
DEFAULT_TPU = TPU_PLATFORMS["TPU v5 lite"]


def platform_for(devices=None) -> TPUPlatform:
    """The modelled platform of a device pool, sized to its device count.

    On a TPU backend the chip is looked up by ``device_kind`` in
    :data:`TPU_PLATFORMS` and an unknown kind raises.  Any other backend
    (the XLA-CPU test hosts) ranks against :data:`DEFAULT_TPU`, the chip
    the designs are built for.
    """
    import jax

    devices = list(devices) if devices is not None else jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        return DEFAULT_TPU.with_chips(len(devices))
    try:
        chip = TPU_PLATFORMS[dev.device_kind]
    except KeyError:
        raise ValueError(
            f"no platform description for TPU device kind "
            f"{dev.device_kind!r} (known: {sorted(TPU_PLATFORMS)}); add "
            "its constants to repro.core.platform.TPU_PLATFORMS"
        ) from None
    return chip.with_chips(len(devices))
