"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` jax reports. One table, with its source; a kind that is
not listed (a CPU included) raises — a share of an assumed chip is not a
measurement. Copied from ``benchmarks/roofline.py`` ``PEAKS`` so that a
later PR can change the program's copy and not the yardstick."""

PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819.0e9,
        "bf16_flops_per_s": 197.0e12,
        "hbm_bytes": 16 * 2 ** 30,
        "source": "Google Cloud documentation, \"TPU v5e\" (per chip: 197 "
                  "bf16 TFLOP/s, 16 GB HBM2e at 819 GB/s)",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peak figures for device_kind {device_kind!r} (known: "
            f"{sorted(PEAKS)}); add them to perf/peaks.py with their "
            "source") from None


def least_seconds(work: dict, device_kind: str, chips: int = 1) -> dict:
    """The least time ``chips`` chips could take for ``work`` (``bytes``
    and ``flops`` the algorithm needs): the larger of bytes over peak HBM
    bandwidth and operations over peak FLOP/s. Says which bounds it."""
    p = peaks(device_kind)
    t_bytes = work["bytes"] / (p["hbm_bytes_per_s"] * chips)
    t_flops = work["flops"] / (p["bf16_flops_per_s"] * chips)
    return {"seconds": max(t_bytes, t_flops),
            "bound": "bytes" if t_bytes >= t_flops else "flops"}
