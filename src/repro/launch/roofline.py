"""Roofline terms from a dry-run record, against a per-backend hardware table.

    t_compute    = HLO_FLOPs_per_dev / peak_flops      (bf16 MXU / FMA peak)
    t_memory     = HLO_bytes_per_dev / mem_bw          (HBM / DRAM bandwidth)
    t_collective = collective_bytes_per_dev / link_bw  (chip-to-chip interconnect)

`MODEL_FLOPS` = 6·N_active·D for training (N = active params, D = tokens) or
2·N_active·D for serving; the ratio against total HLO FLOPs exposes
remat/padding/dispatch waste (brief §Roofline).

The constants live in :data:`HARDWARE`, keyed by a spec name; the process
default comes from :func:`detect_hardware` (the device's ``device_kind``
through :data:`DEVICE_KINDS`; an unknown kind is an error) and can be
forced with ``REPRO_ROOFLINE_HW=<spec name>``.  Every consumer reports the
spec name it used alongside its utilizations.
"""
from __future__ import annotations

import os
from dataclasses import dataclass

HARDWARE_ENV = "REPRO_ROOFLINE_HW"


@dataclass(frozen=True)
class HardwareSpec:
    """Peak rates of one accelerator (per chip)."""

    name: str
    peak_flops: float    # FLOP/s per chip (bf16 where the chip has an MXU)
    mem_bw: float        # bytes/s per chip (HBM / DRAM)
    link_bw: float       # bytes/s of chip-to-chip interconnect per chip


#: spec name → peaks.  ``tpu-v5e``: one TPU v5e chip, 197 TFLOP/s bf16,
#: 819 GB/s HBM, 1,600 Gbit/s (200 GB/s) inter-chip interconnect (Google
#: Cloud documentation, "TPU v5e").  ``cpu-host`` is a deliberately round
#: server-class placeholder (FMA peak, DDR bandwidth, PCIe link) so off-TPU
#: runs label utilizations against an honest denominator instead of a
#: chip they are not running on.
HARDWARE: dict[str, HardwareSpec] = {
    "tpu-v5e":  HardwareSpec("tpu-v5e",  197e12, 819e9, 1600e9 / 8),
    "cpu-host": HardwareSpec("cpu-host", 1e12,   100e9,  32e9),
}

#: ``jax.Device.device_kind`` → :data:`HARDWARE` spec name.  A kind that is
#: not listed has no peaks here: :func:`detect_hardware` refuses it rather
#: than borrow another chip's.
DEVICE_KINDS: dict[str, str] = {
    "TPU v5 lite": "tpu-v5e",
    "cpu": "cpu-host",
}

# legacy module constants (v5e): kept for the dry-run launch path, which
# models v5e pods regardless of where the dry run itself executes.
PEAK_FLOPS = HARDWARE["tpu-v5e"].peak_flops
HBM_BW = HARDWARE["tpu-v5e"].mem_bw
ICI_BW = HARDWARE["tpu-v5e"].link_bw


def detect_hardware() -> str:
    """Map the live jax device to a :data:`HARDWARE` spec name by its
    ``device_kind`` (:data:`DEVICE_KINDS`).

    ``REPRO_ROOFLINE_HW`` overrides detection (it must name a known spec).
    A device kind without an entry raises: numbers computed against the
    wrong machine's peaks are silently wrong.
    """
    forced = os.environ.get(HARDWARE_ENV)
    if forced:
        if forced not in HARDWARE:
            raise ValueError(f"{HARDWARE_ENV}={forced!r} is not one of "
                             f"{sorted(HARDWARE)}")
        return forced
    import jax

    dev = jax.devices()[0]
    name = DEVICE_KINDS.get(dev.device_kind)
    if name is None:
        raise ValueError(f"no roofline peaks for {dev.platform} device kind "
                         f"{dev.device_kind!r} (known: {sorted(DEVICE_KINDS)})")
    return name


def hardware_spec(name: str | None = None) -> HardwareSpec:
    """The spec to compute rooflines against: ``name``, the env override,
    or the detected backend's."""
    return HARDWARE[name or detect_hardware()]


def model_flops(cfg, shp) -> float:
    """Global useful FLOPs for the step (6ND train / 2ND serve)."""
    n_active = cfg.active_param_count()
    if shp.kind == "train":
        tokens = shp.global_batch * shp.seq_len
        return 6.0 * n_active * tokens
    if shp.kind == "prefill":
        tokens = shp.global_batch * shp.seq_len
        return 2.0 * n_active * tokens
    tokens = shp.global_batch * 1  # decode: one new token per sequence
    return 2.0 * n_active * tokens


def lookup_roofline(traffic_bytes: float, flops: float, n_keys: int,
                    measured_s: float | None = None,
                    hw: HardwareSpec | str | None = None) -> dict:
    """Roofline accounting for one engine lookup program.

    ``traffic_bytes``/``flops`` come from the HLO cost analysis
    (:func:`repro.launch.hlo_analysis.analyze_jit`); ``measured_s`` is an
    optional wall-clock for the same batch, turning the bound into a
    utilization.  Returns bytes/key, the memory- and compute-bound floor
    times, the bottleneck, and — when measured — the fraction of the
    bound actually achieved (1.0 = running at the roofline).
    """
    if not isinstance(hw, HardwareSpec):
        hw = hardware_spec(hw)
    t_memory = traffic_bytes / hw.mem_bw
    t_compute = flops / hw.peak_flops
    t_bound = max(t_memory, t_compute)
    out = {
        "hardware": hw.name,
        "bytes_per_key": traffic_bytes / n_keys if n_keys else 0.0,
        "flops_per_key": flops / n_keys if n_keys else 0.0,
        "t_memory_s": t_memory,
        "t_compute_s": t_compute,
        "bottleneck": "memory" if t_memory >= t_compute else "compute",
    }
    if measured_s is not None:
        out["measured_s"] = measured_s
        out["roofline_utilization"] = (t_bound / measured_s
                                       if measured_s > 0 else 0.0)
    return out


def roofline_record(cfg, shp, record: dict) -> dict:
    chips = record["chips"]
    flops_dev = record["cost_analysis"]["flops_per_device"]
    bytes_dev = record["cost_analysis"]["bytes_accessed_per_device"]
    coll_naive = record["collectives"]["total_operand_bytes"]
    coll_ring = record["collectives"]["total_ring_bytes"]

    t_compute = flops_dev / PEAK_FLOPS
    t_memory = bytes_dev / HBM_BW
    t_coll_naive = coll_naive / ICI_BW
    t_coll_ring = coll_ring / ICI_BW

    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll_ring}
    bottleneck = max(terms, key=terms.get)

    mf = model_flops(cfg, shp)
    hlo_total = flops_dev * chips
    useful_ratio = mf / hlo_total if hlo_total else 0.0
    # step time ≈ max(terms) (perfect overlap); roofline fraction = share of
    # the step spent doing useful model math at peak.
    t_step = max(terms.values()) if terms else 0.0
    t_useful = mf / (chips * PEAK_FLOPS)
    return {
        "t_compute": t_compute,
        "t_memory": t_memory,
        "t_collective_naive": t_coll_naive,
        "t_collective_ring": t_coll_ring,
        "bottleneck": bottleneck,
        "model_flops_global": mf,
        "hlo_flops_global": hlo_total,
        "useful_flops_ratio": useful_ratio,
        "roofline_fraction": (t_useful / t_step) if t_step else 0.0,
    }
