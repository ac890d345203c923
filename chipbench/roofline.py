"""Peaks of the chips, and the least bytes each kernel has to move,
computed from its shapes. The yardstick of every `<kernel>_roofline`."""

from __future__ import annotations

# keyed by `jax.devices()[0].device_kind`; source: Google Cloud
# documentation, "TPU v5e" (system architecture)
PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flop_per_s": 197e12},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peaks known for device kind {device_kind!r}: add it to "
            "chipbench/roofline.py with its source")
    return PEAKS[device_kind]


# ops/json_parse.py: the padded uint8 lane carries 32 bytes of tail
PARSE_TAIL_PAD = 32
# per padded line: vals [3] int64, spans [6] int32, flags [14] bool
PARSE_OUT_BYTES_PER_LINE = 3 * 8 + 6 * 4 + 14 * 1


def parse_window_bytes(n_pad: int, l_pad: int) -> int:
    """The JSON field extraction over one window is bound by bytes: it
    has to read the window's lane once and write its output lanes once.
    Its comparisons and scans are a few integer operations a byte, far
    under what the chip computes in the time it moves that byte."""
    return n_pad + PARSE_TAIL_PAD + l_pad * PARSE_OUT_BYTES_PER_LINE


def least_seconds(nbytes: int, device_kind: str) -> float:
    return nbytes / peaks(device_kind)["hbm_bytes_per_s"]
