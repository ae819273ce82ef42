"""Hardware-utilization accounting for the two metrics of record.

Both benches score vs a re-measured CPU baseline; these helpers add the
other axis — what fraction of the CHIP each workload achieves — so "is
it fast, or just faster than one CPU core?" has an on-record answer and
regressions can't hide inside the 8x headroom (VERDICT r4 weak #4).

The models are documented LOWER BOUNDS on real traffic/FLOPs (XLA may
materialize more); achieved rates divide the modeled work by measured
wall-clock, so utilization percentages are conservative.

Peaks live in ONE table, :data:`PEAKS`, keyed by the ``device_kind``
jax reports, each with its source. Asking about a kind the table does
not list — a CPU included — raises: a utilization against an assumed
chip is not a measurement. Neither workload is MXU-bound: word2vec at
dim=100 does ~3.6 KFLOP per pair against ~8 KB of embedding-row traffic
(arithmetic intensity ~0.4 FLOP/byte — three orders below the MXU's
balance point), and the LDA sampler's dominant term is one random 2 KB
bf16 word-row gather per token. For such random-row access the
practical ceiling is the gather engine, not sequential-peak HBM, so a
measured random-gather rate rides along as
``measured_gather_ceiling_gbps`` with the probe that produced it.
"""

PEAKS = {
    # jax's device_kind for one TPU v5e chip
    "TPU v5 lite": {
        "hbm_gbps": 819.0,
        "bf16_tflops": 197.0,
        "source": "Google Cloud documentation, \"TPU v5e\" (per chip: "
                  "197 bf16 TFLOP/s, 16 GB HBM2e at 819 GB/s)",
        # random 2 KB-row gather rate, ordering-independent — the
        # row-fetch engine's ceiling
        "measured_gather_ceiling_gbps": 68.0,
        "gather_ceiling_source":
            "benchmarks/experiments/lda_gather_order_probe.py, a v5e "
            "chip run of 2026-07; not re-measured on the current machine",
    },
}


def peaks(device_kind: str) -> dict:
    """The peak figures of one device kind; unknown kinds raise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peak figures for device_kind {device_kind!r} (known: "
            f"{sorted(PEAKS)}); add them to benchmarks/roofline.py "
            "PEAKS with their source") from None


def w2v_utilization(pairs_per_sec: float, dim: int, negative: int, *,
                    device_kind: str) -> dict:
    """Roofline fields for the w2v engine tier.

    FLOP model per pair (fused scan superstep, f32):
      forward logits   src . tgt_k for k in 1+negative  -> 2*(1+n)*D
      backward d_src   err @ tgts                       -> 2*(1+n)*D
      backward d_tgt   err^T outer src                  -> 2*(1+n)*D
    HBM model per pair: 2+negative embedding rows (1 src, 1+n tgt) of
    4*D bytes each -- gathered (read), scatter-added back
    (read-modify-write = read + write): 3 * (2+n) * 4*D bytes.
    """
    peak = peaks(device_kind)
    flops_per_pair = 6.0 * (1 + negative) * dim
    bytes_per_pair = 3.0 * (2 + negative) * 4 * dim
    achieved_tflops = pairs_per_sec * flops_per_pair / 1e12
    achieved_gbps = pairs_per_sec * bytes_per_pair / 1e9
    return {
        "device_kind": device_kind,
        "model_flops_per_pair": round(flops_per_pair),
        "model_hbm_bytes_per_pair": round(bytes_per_pair),
        "achieved_tflops": round(achieved_tflops, 4),
        "mxu_peak_tflops": peak["bf16_tflops"],
        "mxu_util_pct": round(100 * achieved_tflops
                              / peak["bf16_tflops"], 3),
        "achieved_hbm_gbps": round(achieved_gbps, 2),
        "hbm_peak_gbps": peak["hbm_gbps"],
        "hbm_util_pct": round(100 * achieved_gbps / peak["hbm_gbps"], 2),
    }


def lda_utilization(doc_tokens_per_sec: float, num_topics: int,
                    vocab: int, tokens: int,
                    block_tokens: int = 512, *,
                    device_kind: str) -> dict:
    """Roofline fields for the doc-blocked LDA sampler.

    HBM model per token (the doc-blocked sampler, sampler="tiled"):
      w_gather    one bf16 word row [K]                   -> 2*K bytes
      z           int32 read + write                      -> 8
      stream      packed token ~8 B (measured fill)       -> 8
      doc blocks  [16, K/128, 128] int16 in+out per
                  block_tokens-token kernel block         -> 64*K/block
      rebuild     per sweep: scatter z into the int32
                  [V, K] master + rewrite the bf16 mirror -> 6*V*K/T
    The dominant term is the random 2 KB w_gather, so utilization is
    also scored against the MEASURED gather-engine ceiling (see module
    docstring), not just sequential-peak HBM.
    """
    peak = peaks(device_kind)
    ceiling = peak["measured_gather_ceiling_gbps"]
    k = float(num_topics)
    w_gather = 2.0 * k
    per_token = (w_gather + 8.0 + 8.0 + 64.0 * k / block_tokens
                 + 6.0 * vocab * k / tokens)
    achieved_gbps = doc_tokens_per_sec * per_token / 1e9
    gather_gbps = doc_tokens_per_sec * w_gather / 1e9
    return {
        "device_kind": device_kind,
        "model_hbm_bytes_per_token": round(per_token, 1),
        "achieved_hbm_gbps": round(achieved_gbps, 2),
        "hbm_peak_gbps": peak["hbm_gbps"],
        "hbm_util_pct": round(100 * achieved_gbps / peak["hbm_gbps"], 2),
        "w_gather_gbps": round(gather_gbps, 2),
        "measured_gather_ceiling_gbps": ceiling,
        "gather_ceiling_util_pct": round(100 * gather_gbps / ceiling, 1),
    }
