"""Analytical overhead model (paper §3.4).

"The total run time of a machine learning task is composed of computation and
memory access. [...] our protection methods additionally introduce encryption,
decryption, and message authentication, all of which are bound to memory
access."  Slowdown therefore scales with memory-access *intensity* (words per
FLOP): ~1 word/FLOP for GEMV (the paper's FC rows) vs ~1/(Ho*Wo) for conv.

This module predicts the slowdown of a (workload, accelerator, protection)
triple.  It backs two things:
  * the VTA cycle simulator calibration (benchmarks/table1_vta.py),
  * the TPU sealed-step cost estimates in the roofline analysis, where the
    crypto term rides on the HBM-bytes term exactly as the paper's crypto
    engine rides on DRAM access.
"""
from __future__ import annotations

import dataclasses

from .policy import Protection


@dataclasses.dataclass(frozen=True)
class Workload:
    name: str
    flops: float            # useful MACs*2
    bytes_read: float       # DRAM reads touched by the engine
    bytes_written: float    # DRAM writes

    @property
    def bytes_total(self) -> float:
        return self.bytes_read + self.bytes_written

    @property
    def intensity_words_per_flop(self) -> float:
        return (self.bytes_total / 4.0) / max(self.flops, 1.0)


@dataclasses.dataclass(frozen=True)
class AcceleratorModel:
    """Throughput/latency model of an accelerator + its security layer."""
    name: str
    flops_per_cycle: float          # core compute rate
    dram_bytes_per_cycle: float     # DRAM bandwidth at the interface
    # crypto engine: counter-mode unit (pipelined) and MAC unit
    ctr_bytes_per_cycle: float      # keystream+XOR throughput (pipelined AES/ARX)
    ctr_pipeline_latency: float     # cycles to fill the pipe (paper: 29)
    mac_cycles_per_16b: float       # cycles per 128-bit block of MAC input
    mac_pipelined: bool             # paper's GFM: False (serial); tree MAC: True
    chunk_bytes: int = 2048         # MAC verification granularity s

    def crypto_cycles(self, n_bytes: float, encrypts: bool = True,
                      authenticates: bool = True) -> float:
        """Crypto-engine cycles to seal/unseal ``n_bytes`` through the
        memory path.  Shared by ``step_cycles`` and the cost-attribution
        ledger (obs/costs.py ``CostLedger.reconcile``), so the per-phase
        drift report prices bytes with exactly the model the roofline uses.
        """
        if n_bytes <= 0:
            return 0.0
        crypto = 0.0
        if encrypts:
            # CTR is pipelined: adds latency per chunk but streams at full rate.
            n_chunks = max(1.0, n_bytes / self.chunk_bytes)
            crypto += (n_bytes / self.ctr_bytes_per_cycle
                       + n_chunks * self.ctr_pipeline_latency)
        if authenticates:
            blocks = n_bytes / 16.0
            if self.mac_pipelined:
                # tree MAC: log-depth, streams with the fetch; model as an
                # extra pass at CTR-like throughput plus per-chunk log depth.
                n_chunks = max(1.0, n_bytes / self.chunk_bytes)
                import math
                depth = math.ceil(math.log2(max(2.0, self.chunk_bytes / 16.0)))
                crypto += blocks + n_chunks * depth
            else:
                # paper's serial GFM: ceil(s/128bit) * 8 cycles, fully serial,
                # NOT overlapped with the fetch stream.
                crypto += blocks * self.mac_cycles_per_16b
        return crypto

    def step_cycles(self, w: Workload, prot: Protection) -> float:
        """Cycle estimate: compute/memory overlap, crypto bound to memory path."""
        compute = w.flops / self.flops_per_cycle
        mem = w.bytes_total / self.dram_bytes_per_cycle
        crypto = self.crypto_cycles(w.bytes_total, encrypts=prot.encrypts,
                                    authenticates=prot.authenticates)
        # compute overlaps with (mem + crypto) up to the max (double buffering);
        # serial MAC does not overlap, which the max() structure captures since
        # crypto inflates the memory-path term.
        return max(compute, mem + crypto)

    def slowdown(self, w: Workload, prot: Protection) -> float:
        return self.step_cycles(w, prot) / self.step_cycles(w, Protection.NONE)


# TPU v5e single-chip constants (used for roofline-style estimates).
# Peaks: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16 and
# 819 GB/s HBM per chip.  The crypto rates are this repo's model, not
# measurements.
TPU_V5E = AcceleratorModel(
    name="tpu-v5e-sealed",
    flops_per_cycle=197e12 / 940e6,      # bf16 peak @ ~940 MHz
    dram_bytes_per_cycle=819e9 / 940e6,  # HBM BW
    ctr_bytes_per_cycle=8 * 128 * 4 / 4,  # VPU: 8x128 lanes, ~4 cyc/word ARX amortized
    ctr_pipeline_latency=20.0,
    mac_cycles_per_16b=1.0,              # tree MAC streams
    mac_pipelined=True,
    chunk_bytes=2048,
)


# one row per chip, keyed by jax's ``device.device_kind``; a device with no
# row (the CPU among them) is priced by no model, never as a chip it is not
MODELS_BY_DEVICE_KIND = {"TPU v5 lite": TPU_V5E}


def gemm_workload(name: str, m: int, n: int, k: int, dtype_bytes: int = 1,
                  batch: int = 1) -> Workload:
    flops = 2.0 * batch * m * n * k
    reads = batch * (m * k + k * n) * dtype_bytes
    writes = batch * m * n * dtype_bytes
    return Workload(name, flops, reads, writes)


def conv2d_workload(name: str, h: int, w: int, cin: int, cout: int,
                    kh: int, kw: int, dtype_bytes: int = 1, batch: int = 1,
                    stride: int = 1, pad: int | None = None) -> Workload:
    if pad is None:
        pad = kh // 2
    ho = (h + 2 * pad - kh) // stride + 1
    wo = (w + 2 * pad - kw) // stride + 1
    flops = 2.0 * batch * ho * wo * cout * cin * kh * kw
    reads = (batch * h * w * cin + kh * kw * cin * cout) * dtype_bytes
    writes = batch * ho * wo * cout * dtype_bytes
    return Workload(name, flops, reads, writes)
