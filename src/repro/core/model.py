"""SASA analytical performance model (paper Section 4.2) + TPU re-derivation.

Part 1 — paper-exact model (Eqs. 1-9) in FPGA cycles for the Alveo U280.
  Used to reproduce the paper's own parallelism decisions (Table 3) and the
  SODA-vs-SASA speedups (Sec. 5.4).  Resource estimates per PE are a
  microarchitectural byte/op model calibrated against the paper's reported
  max-PE counts (Figs. 18-20); they stand in for the Vitis HLS synthesis
  report that step 2 of the paper's tool flow runs.

Part 2 — TPU model.  Same five parallelism variants, re-derived for the TPU
  memory hierarchy:

    FPGA concept                      TPU concept
    ------------                      -----------
    PE streaming one HBM bank         chip streaming its own HBM
    U parallel PUs (512b AXI)         8x128 VPU lanes on a VMEM tile
    s cascaded PEs (FIFO dataflow)    s fused stencil iterations per VMEM
                                      residency (temporal blocking)
    k PEs on k HBM banks              k chips, grid row-sharded (shard_map)
    border streaming wires            jax.lax.ppermute over ICI
    redundant halo compute            redundant halo compute (identical)

  Latency per round = max(compute, HBM, ICI-bandwidth) + ICI latency terms,
  times the number of rounds ceil(iter/s).  The model returns all three
  roofline terms so the auto-tuner can report the dominant bottleneck.
"""
from __future__ import annotations

import dataclasses
import math
from repro.core.platform import FPGAPlatform, TPUPlatform
from repro.core.spec import BinOp, Call, Neg, StencilSpec, walk

VARIANTS = ("temporal", "spatial_r", "spatial_s", "hybrid_r", "hybrid_s")


class InfeasibleDesign(ValueError):
    """A candidate configuration that cannot be built on this pool or for
    this spec (a guard :func:`repro.core.analysis.preflight` mirrors).

    The feasibility retry loops skip to the next candidate on this error
    only; any other exception from a build — a Pallas lowering refusal
    among them — propagates.
    """


@dataclasses.dataclass(frozen=True)
class ParallelismConfig:
    """A point in the SASA design space."""

    variant: str          # one of VARIANTS
    k: int = 1            # degree of spatial parallelism (devices / PE groups)
    s: int = 1            # degree of temporal parallelism (stages / fusion depth)
    tile_rows: int = 0    # TPU only: Pallas row-tile B (0 = executor default)
    batch_tile: int = 0   # TPU only: batch entries folded into the kernel grid
                          # per step (0 = whole batch under vmap)
    buffer_depth: int = 0  # TPU only: explicit HBM->VMEM buffers per stream.
                          # 0 = one-shot whole-block kernels under vmap
                          # (copy/compute overlap left to XLA); >= 2 = the
                          # explicitly double-buffered tile pipeline.

    def __post_init__(self):
        assert self.variant in VARIANTS, self.variant
        assert self.batch_tile >= 0, self.batch_tile
        assert self.buffer_depth in (0,) or self.buffer_depth >= 2, (
            "buffer_depth is 0 (vmapped one-shot) or >= 2 (pipelined); "
            "a single buffer cannot overlap copy with compute"
        )

    @property
    def devices_needed(self) -> int:
        """Device count this config occupies (temporal stages map to
        devices; every executor must size device pools from this)."""
        return max(self.s, 1) if self.variant == "temporal" else max(self.k, 1)


@dataclasses.dataclass(frozen=True)
class Prediction:
    config: ParallelismConfig
    latency: float              # seconds
    compute_term: float         # seconds
    memory_term: float          # seconds
    collective_term: float      # seconds
    collective_bytes: float     # per-device bytes over the whole run
    hbm_bytes: float            # per-device bytes over the whole run
    flops: float                # per-device ops over the whole run
    rounds: int
    vmem_bytes: float = 0.0     # peak VMEM working set the design schedules
    notes: str = ""

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.compute_term,
            "memory": self.memory_term,
            "collective": self.collective_term,
        }
        return max(terms, key=terms.get)

    @property
    def gcells_per_s(self) -> float:
        return 0.0  # filled by caller with grid knowledge; see predict()


# ===========================================================================
# Part 1: paper-exact FPGA model (Eqs. 1-9)
# ===========================================================================


def _op_mix(spec: StencilSpec) -> dict[str, int]:
    mix = {"add": 0, "mul": 0, "div": 0, "cmp": 0}
    for stage in spec.stages:
        for node in walk(stage.expr):
            if isinstance(node, BinOp):
                if node.op in "+-":
                    mix["add"] += 1
                elif node.op == "*":
                    mix["mul"] += 1
                else:
                    mix["div"] += 1
            elif isinstance(node, Call):
                mix["cmp"] += max(len(node.args) - 1, 1)
            elif isinstance(node, Neg):
                mix["add"] += 1
    return mix


def estimate_pe_resources(
    spec: StencilSpec, fpga: FPGAPlatform, U: int = 16
) -> dict[str, float]:
    """Per-PE resource vector (stand-in for the Vitis HLS synthesis report).

    Cost constants are fp32 operator costs on UltraScale+ (DSP48E2), with
    streaming infrastructure overhead calibrated so the derived max-PE
    counts match the paper's Figs. 18-20 (JACOBI2D 21, DILATE 18,
    HOTSPOT 9, others 9-15 on U280).
    """
    mix = _op_mix(spec)
    # DSPs: fp32 add/sub=2, mul=3, div=0 (LUT-heavy), cmp=0; one op set per PU.
    dsp = U * (2 * mix["add"] + 3 * mix["mul"])
    # LUTs: per-PU datapath + per-PE streaming infra + reuse-buffer muxing.
    lut = (
        9_000  # AXI-stream plumbing, control FSM
        + U * (120 * mix["add"] + 90 * mix["mul"] + 3_000 * mix["div"]
               + 150 * mix["cmp"])
        + 250 * spec.points * (1 + spec.radius)
    )
    ff = 2.2 * lut
    # BRAM: coalesced reuse buffer holds `halo` rows of every streamed input
    # at 512b width (Sec. 3.1).  4.5 KiB per BRAM36.
    reuse_bytes = (
        spec.halo * spec.cols_flat * spec.itemsize * max(spec.num_inputs, 1)
    )
    bram = max(2.0, reuse_bytes / 4608) + 4 * spec.num_inputs
    return {"lut": lut, "ff": ff, "dsp": float(dsp), "bram": bram}


def fpga_pe_res(spec: StencilSpec, fpga: FPGAPlatform, U: int = 16) -> int:
    """Eq. 1: resource-bound PE count."""
    res = estimate_pe_resources(spec, fpga, U)
    avail = {
        "lut": fpga.luts,
        "ff": fpga.ffs,
        "dsp": fpga.dsps,
        "bram": fpga.brams,
    }
    bound = min(fpga.alpha * avail[r] / max(res[r], 1e-9) for r in avail)
    return max(int(bound), 1)


def fpga_pe_bw(spec: StencilSpec, fpga: FPGAPlatform) -> int:
    """Eq. 2: bandwidth-bound spatial PE count."""
    banks_per_pe = spec.num_inputs + 1
    return max((fpga.hbm_banks - fpga.reserved_banks) // banks_per_pe, 1)


def fpga_max_pe(spec: StencilSpec, fpga: FPGAPlatform, s: int = 1) -> int:
    """Eq. 3 (temporal stages need no extra bandwidth)."""
    return min(fpga_pe_res(spec, fpga), fpga_pe_bw(spec, fpga) * max(s, 1))


def _fpga_latency_cycles(
    spec: StencilSpec, cfg: ParallelismConfig, fpga: FPGAPlatform, U: int = 16
) -> float:
    """Eqs. 4-8, verbatim (two-dimensional view: R rows x C flat columns)."""
    R, C = spec.rows, spec.cols_flat
    it = spec.iterations
    r = spec.radius
    d = halo = 2 * r
    k, s = cfg.k, cfg.s
    if cfg.variant == "temporal":
        return math.ceil((R + d * (s - 1)) * C / U) * math.ceil(it / s)
    if cfg.variant == "spatial_r":
        iter_avg = it / 2.0  # paper: halo shrinks over iterations, avg iter/2
        return math.ceil((math.ceil(R / k) + halo * iter_avg) * C / U) * it
    if cfg.variant == "spatial_s":
        return math.ceil((math.ceil(R / k) + halo) * C / U) * it
    if cfg.variant == "hybrid_r":
        iter_avg = it / 2.0
        return (
            math.ceil((math.ceil(R / k) + halo * iter_avg) * C / U)
            * math.ceil(it / s)
        )
    if cfg.variant == "hybrid_s":
        return (
            math.ceil((math.ceil(R / k) + halo * s) * C / U)
            * math.ceil(it / s)
        )
    raise ValueError(cfg.variant)


def predict_fpga(
    spec: StencilSpec, cfg: ParallelismConfig, fpga: FPGAPlatform, U: int = 16
) -> Prediction:
    cycles = _fpga_latency_cycles(spec, cfg, fpga, U)
    lat = cycles / fpga.freq_hz
    # Roofline bookkeeping for reporting parity with the TPU model.
    hbm = spec.cells * spec.itemsize * (spec.num_inputs + 1)
    if cfg.variant in ("spatial_r", "spatial_s"):
        hbm *= spec.iterations
    else:
        hbm *= math.ceil(spec.iterations / max(cfg.s, 1))
    return Prediction(
        config=cfg,
        latency=lat,
        compute_term=lat,
        memory_term=hbm / (cfg.k * fpga.bank_bw * max(spec.num_inputs, 1)),
        collective_term=0.0,
        collective_bytes=0.0,
        hbm_bytes=hbm / max(cfg.k, 1),
        flops=spec.cells * spec.ops_per_cell * spec.iterations / max(cfg.k, 1),
        rounds=math.ceil(spec.iterations / max(cfg.s, 1)),
    )


def fpga_candidate_configs(
    spec: StencilSpec,
    fpga: FPGAPlatform,
    U: int = 16,
    pe_res_override: int | None = None,
) -> list[ParallelismConfig]:
    """Step 3 of the tool flow (Sec. 4.3): the candidate set the paper explores.

    ``pe_res_override`` lets callers substitute a synthesizer-reported
    resource-bound PE count (the paper obtains this from Vitis HLS, Figs.
    18-20) for our analytical resource estimate.
    """
    pe_res = pe_res_override or fpga_pe_res(spec, fpga, U)
    pe_bw = fpga_pe_bw(spec, fpga)
    out = []
    # temporal: s_t = #PE_res, capped by iteration count
    out.append(ParallelismConfig("temporal", k=1, s=min(pe_res, spec.iterations)))
    # spatial: k = Max#PE (s=1)
    max_pe1 = min(pe_res, pe_bw)
    out.append(ParallelismConfig("spatial_r", k=max_pe1, s=1))
    out.append(ParallelismConfig("spatial_s", k=max_pe1, s=1))
    # hybrid: k multiple of #SLRs, k*s <= Max#PE(s), k <= PE_bw
    for k in range(fpga.num_slrs, pe_bw + 1, fpga.num_slrs):
        s = max(min(pe_res // k, spec.iterations), 1)
        if s >= 1 and k * s <= pe_res:
            out.append(ParallelismConfig("hybrid_r", k=k, s=s))
            out.append(ParallelismConfig("hybrid_s", k=k, s=s))
    return out


# ===========================================================================
# Part 2: TPU model
# ===========================================================================


def vmem_fusion_limit(
    spec: StencilSpec, tpu: TPUPlatform, tile_rows: int
) -> int:
    """Max fusion depth s such that a (B + 2sr) x C_pad tile (double-buffered,
    all streamed inputs + output + one intermediate) fits in VMEM.

    This is the TPU analogue of Eq. 1's resource bound: FPGA LUT/DSP/BRAM
    capacity becomes VMEM capacity.
    """
    r = spec.radius
    C = spec.cols_flat
    n_arrays = spec.num_inputs + 2  # inputs + working copy + output
    s = 1
    while True:
        rows = tile_rows + 2 * (s + 1) * r
        cpad = _round_up(C + 2 * (s + 1) * r, 128)
        if rows * cpad * spec.itemsize * n_arrays * 2 > tpu.vmem_bytes:
            return max(s, 1)
        s += 1
        if s > 256:
            return 256


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def predict_tpu(
    spec: StencilSpec,
    cfg: ParallelismConfig,
    tpu: TPUPlatform,
    iterations: int | None = None,
) -> Prediction:
    """TPU latency model for one parallelism configuration.

    Derivation mirrors Eqs. 4-8 but in seconds against the chip roofline:

      * a fused-s kernel pass reads (inputs) and writes (1) each grid cell
        once per round -> HBM term;
      * fused iterations recompute a trapezoid halo: iteration t of a round
        computes (rows_local + 2*r*(s-t)) rows -> compute term;
      * spatial_s exchanges r rows/iteration, hybrid_s s*r rows/round,
        *_r variants exchange iter*r rows once -> collective term.
    """
    it = spec.iterations if iterations is None else iterations
    R, C = spec.rows, spec.cols_flat
    r = spec.radius
    ops = spec.ops_per_cell
    k, s = cfg.k, cfg.s
    itemsize = spec.itemsize
    n_in = spec.num_inputs

    if cfg.variant == "temporal":
        k = 1
    if cfg.variant in ("spatial_r", "spatial_s"):
        s = 1
    s = max(min(s, it), 1)
    rounds = math.ceil(it / s)
    rows_local = math.ceil(R / k)

    # ---- redundant halo rows computed per round (per device) ----
    if cfg.variant in ("spatial_r", "hybrid_r"):
        # halo depth at iteration t (global) is (it - t) * r, averaged it/2
        redundant_rows_per_iter = 2 * r * (it / 2.0) if k > 1 else 0.0
    elif cfg.variant in ("spatial_s", "hybrid_s"):
        redundant_rows_per_iter = 2 * r * ((s - 1) / 2.0) if k > 1 else 0.0
    else:  # temporal: fused trapezoid within the single device's tiles
        redundant_rows_per_iter = 0.0

    # fused-kernel trapezoid overhead inside each tile (any fused variant):
    tile = cfg.tile_rows or 256
    n_tiles = math.ceil(rows_local / tile)
    trapezoid_rows_per_iter = 2 * r * ((s - 1) / 2.0) * n_tiles

    compute_rows = (
        rows_local + redundant_rows_per_iter + trapezoid_rows_per_iter
    ) * it
    flops = compute_rows * C * ops
    compute_term = flops / tpu.vpu_flops_f32

    # ---- HBM traffic ----
    # per round: read all inputs (+halo overlap), write output once.
    halo_rows_read = 2 * s * r * n_tiles
    bytes_per_round = (
        (n_in * (rows_local + halo_rows_read) + rows_local)
        * C * itemsize
    )
    hbm_bytes = bytes_per_round * rounds
    memory_term = hbm_bytes / tpu.hbm_bw

    # ---- ICI ----
    if k <= 1:
        coll_bytes, n_msgs = 0.0, 0
    elif cfg.variant in ("spatial_r", "hybrid_r"):
        coll_bytes = 2 * min(it * r, rows_local) * C * itemsize * n_in
        n_msgs = 2
    elif cfg.variant == "spatial_s":
        coll_bytes = 2 * r * C * itemsize * it
        n_msgs = 2 * it
    else:  # hybrid_s
        coll_bytes = 2 * min(s * r, rows_local) * C * itemsize * rounds
        n_msgs = 2 * rounds
    collective_term = coll_bytes / tpu.ici_bw + n_msgs * tpu.ici_latency

    # ---- VMEM footprint / pipeline overlap ----
    # Working set of one (tile + 2sr) x C_pad residency: every streamed
    # input block, one working copy, one output block.
    in_rows = tile + 2 * s * r
    cpad = _round_up(C + 2 * s * r, 128)
    tile_bytes = in_rows * cpad * itemsize * (n_in + 2)
    if cfg.buffer_depth >= 2:
        # Explicitly pipelined tile loop: HBM->VMEM copies for step i+1 are
        # issued while step i computes, so copy/compute overlap is scheduled
        # rather than hoped for.  The price is the pipeline fill — the
        # (depth-1) tile transfers before the first compute of each round —
        # and a buffer_depth-deep VMEM footprint.
        # One fill per kernel launch (per round); with the batch axis
        # folded into the grid the launch streams batch_tile * n_tiles
        # tiles, so per-grid fill cost amortizes over both.
        vmem_bytes = float(cfg.buffer_depth * tile_bytes)
        steps_per_launch = max(n_tiles * max(cfg.batch_tile, 1), 1)
        fill_term = (
            (cfg.buffer_depth - 1)
            * memory_term / max(steps_per_launch, 1)
        )
        overlap_penalty = 0.0
        notes = "tile-pipelined"
        if vmem_bytes > tpu.vmem_bytes:
            # Infeasible residency: the schedule would thrash VMEM.  Keep
            # the candidate rankable but never preferable.
            fill_term += memory_term + compute_term
            notes = "tile-pipelined (VMEM overflow)"
    else:
        # One-shot whole-block kernels under vmap: XLA's implicit double
        # buffering overlaps only part of the copy with compute, so the
        # hidden term leaks back into latency (modelled as half the
        # smaller roofline term, the overhead-decomposition idiom).
        vmem_bytes = float(2 * tile_bytes)
        fill_term = 0.0
        overlap_penalty = 0.5 * min(compute_term, memory_term)
        notes = ""

    # Dataflow overlap: compute and HBM stream concurrently (the TPU DMA
    # engine double-buffers VMEM tiles), collectives serialize with rounds
    # only for the *_s variants; *_r pay it once up front.
    latency = (
        max(compute_term, memory_term)
        + overlap_penalty + fill_term + collective_term
    )
    return Prediction(
        config=cfg,
        latency=latency,
        compute_term=compute_term,
        memory_term=memory_term,
        collective_term=collective_term,
        collective_bytes=coll_bytes,
        hbm_bytes=hbm_bytes,
        flops=flops,
        rounds=rounds,
        vmem_bytes=vmem_bytes,
        notes=notes,
    )


def tpu_candidate_configs(
    spec: StencilSpec, tpu: TPUPlatform, iterations: int | None = None
) -> list[ParallelismConfig]:
    """Enumerate the design space on a TPU slice (analogue of Sec. 4.3 step 3)."""
    it = spec.iterations if iterations is None else iterations
    R = spec.rows
    r = spec.radius
    n = tpu.num_chips
    ks = sorted({k for k in range(1, n + 1) if n % k == 0})
    tile = 256
    s_max_vmem = vmem_fusion_limit(spec, tpu, tile)
    out: list[ParallelismConfig] = []
    for s in _fusion_depths(min(it, s_max_vmem)):
        out.append(ParallelismConfig("temporal", k=1, s=s, tile_rows=tile))
        # Batch-in-grid tile pipeline: same fusion depth, but the batch
        # axis is folded into the kernel grid and HBM->VMEM copies are
        # explicitly double-buffered.  vmem_fusion_limit already bounds s
        # to a 2-deep residency, so depth-2 candidates are always feasible.
        out.append(ParallelismConfig(
            "temporal", k=1, s=s, tile_rows=tile,
            batch_tile=8, buffer_depth=2,
        ))
    for k in ks:
        if k == 1:
            continue
        rows_local = R // k
        if rows_local < 2 * r:
            continue
        if it * r <= rows_local:
            out.append(ParallelismConfig("spatial_r", k=k, s=1, tile_rows=tile))
        out.append(ParallelismConfig("spatial_s", k=k, s=1, tile_rows=tile))
        for s in _fusion_depths(min(it, s_max_vmem)):
            if s <= 1:
                continue
            if s * r <= rows_local:
                out.append(
                    ParallelismConfig("hybrid_s", k=k, s=s, tile_rows=tile)
                )
            if it * r <= rows_local:
                out.append(
                    ParallelismConfig("hybrid_r", k=k, s=s, tile_rows=tile)
                )
    return out


def _fusion_depths(s_max: int) -> list[int]:
    out = [1]
    s = 2
    while s <= s_max:
        out.append(s)
        s *= 2
    if s_max not in out and s_max > 1:
        out.append(s_max)
    return out


def choose_best(
    spec: StencilSpec,
    platform,
    iterations: int | None = None,
    pe_res_override: int | None = None,
    tie_eps: float = 0.05,
    optimize: bool = True,
) -> list[Prediction]:
    """Eq. 9: rank candidate configurations by predicted latency.

    Configurations within ``tie_eps`` of the fastest are re-ranked by
    resource efficiency (fewest spatial groups = fewest HBM banks / ICI
    links), matching the paper's "choose the most resource-efficient one"
    tie-break (Sec. 4.3 step 3).

    With ``optimize`` (the default) the spec is first lowered through the
    IR pass pipeline (:mod:`repro.core.ir`), so compute terms and op-mix
    resource estimates are derived from *post-optimization* op counts —
    the counts the executors actually run — rather than the raw DSL's.
    Callers that already hold a lowered spec pass ``optimize=False``.
    """
    if optimize:
        from repro.core.ir import lower

        spec = lower(spec).spec
    if isinstance(platform, FPGAPlatform):
        cfgs = fpga_candidate_configs(spec, platform, pe_res_override=pe_res_override)
        preds = [predict_fpga(spec, c, platform) for c in cfgs]
    else:
        cfgs = tpu_candidate_configs(spec, platform, iterations)
        preds = [predict_tpu(spec, c, platform, iterations) for c in cfgs]
    preds.sort(key=lambda p: p.latency)
    best = preds[0].latency
    near = [p for p in preds if p.latency <= best * (1 + tie_eps)]
    rest = [p for p in preds if p.latency > best * (1 + tie_eps)]
    near.sort(key=lambda p: (p.config.k, p.latency, -p.config.s))
    return near + rest
