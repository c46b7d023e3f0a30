"""Peak rates of the chips the benchmark runs on, and the bytes a lookup
must move: the yardstick of every roofline share it reports.

Source of the v5e row: Google Cloud documentation, "TPU v5e" — per chip
197 TFLOP/s (bf16), 393 TOP/s (int8), 16 GB of HBM at 819 GB/s, and
1,600 Gbit/s of inter-chip interconnect.
"""
from __future__ import annotations

#: ``jax.Device.device_kind`` → peaks of one chip
PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flop_per_s": 197e12,
                    "int8_op_per_s": 393e12, "hbm_bytes": 16e9,
                    "ici_bits_per_s": 1600e9},
}

#: The least any lookup moves per key: the 4-byte key read in and the
#: 4-byte bucket written out.  Table reads can hit on-chip memory, so they
#: are not counted; the share of the roofline this gives cannot pass 100%.
LOOKUP_BYTES_PER_KEY = 8


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a chip not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peaks for device kind {device_kind!r}; "
                         f"known: {sorted(PEAKS)}") from None


def lookup_floor_s(keys: int, device_kind: str) -> float:
    """Seconds the chip needs at least to look up ``keys`` keys: their
    bytes over the HBM bandwidth (no lookup is bound by arithmetic)."""
    return keys * LOOKUP_BYTES_PER_KEY / peaks(device_kind)["hbm_bytes_per_s"]
