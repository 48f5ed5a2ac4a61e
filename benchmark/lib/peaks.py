"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` JAX reports. A device that is not in the table is an
error, never a default: a roofline or utilisation share against the
wrong peak is worse than none."""

from __future__ import annotations

#: Source: Google Cloud documentation, "TPU v5e" system architecture
#: page: 197 TFLOP/s bf16 and 393 TOP/s int8 per chip, 16 GB of HBM2e at
#: 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect. JAX reports the
#: v5e as ``TPU v5 lite`` (PERF.md, PR 21).
PEAKS: dict[str, dict] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2 ** 30,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}


class UnknownDevice(LookupError):
    """The device is not in the peaks table."""


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"device_kind {device_kind!r} is not in the peaks table "
            f"(known: {sorted(PEAKS)}); add it with its source before "
            "measuring on it") from None
