"""Operations and bytes a unit of work needs, from its shapes alone.

Each function counts what the algorithm requires, not what one
implementation happens to do, so the count stays the same when a later
change replaces the code that does the work. Every count is a lower
bound: a share of the roofline computed from it cannot pass 100% unless
the time measured leaves out part of the work.

``least_seconds`` turns a count into the least time on a chip from the
peaks table and says which bound applies.
"""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")
F32 = 4


def peaks(device_kind: str) -> dict:
    """Peaks of one chip; a kind missing from the table is an error."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE}; known: {sorted(table)}")
    return table[device_kind]


def least_seconds(work: dict, peak: dict):
    """(seconds, bound) of a count {"flops", "bytes"}: the larger of the
    compute time at the bf16 peak and the memory time at the HBM
    bandwidth, and which one it is."""
    compute = work["flops"] / peak["bf16_flops_per_s"]
    memory = work["bytes"] / peak["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")


def cd_sweep(n: int, p: int) -> dict:
    """One coordinate-descent sweep of the Cox objective over p columns.

    Per coordinate the update needs the stabilized hazards w = exp(eta -
    m) (n exp), w x and w x^2 (2n), three suffix sums (3n), the risk-set
    means m1, m2 (2n), the derivatives g = sum delta (m1 - x) and
    h = sum delta (m2 - m1^2) (7n) and the eta update (2n): 17n
    operations. Bytes: the feature matrix read once, and eta (read and
    written), delta and the risk-set start index once each per sweep —
    the least any implementation must move, since every n-vector fits in
    on-chip memory at the sizes benchmarked.
    """
    return {"flops": 17.0 * n * p,
            "bytes": F32 * (n * p + 4.0 * n)}


def score_batch(b: int, p: int, g: int) -> dict:
    """One scoring batch of b requests against a dense model of p
    coefficients and a survival grid of g points, returning risk and
    median (no curves).

    Operations: x beta (2bp), exp (b), the S(t) panel exp(-H0 r) (2bg)
    and the median search over it (bg). Bytes: the request features, the
    coefficients and the baseline read once, risk and median written
    once. The (b, g) panel is not counted as traffic: a fused evaluation
    never writes it. Adapted from the serving kernels' shape functions
    (``_cost_survival_curves`` and ``_cost_risk_dense``), which count the
    panel write as well.
    """
    return {"flops": 2.0 * b * p + b + 3.0 * b * g,
            "bytes": F32 * (b * p + p + g + 2.0 * b)}


def mamba2_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Model operations per token of one training step (forward and
    backward, 3x forward) of a Mamba-2 stack under a pooled linear head.

    Per layer, forward: the input projection 2 D (2 E + 2 G N + H), the
    depthwise causal convolution 2 W (E + 2 G N), the selective-state
    recurrence in its linear form (state decay and input outer product
    3 H P N, read-out 2 H P N, skip 2 H P) and the output projection
    2 E D, with E = expand * D and H = E / P. The embedding is a gather
    and the norms and gates are elementwise: neither is counted.
    The head, 2 D per sequence, is spread over its seq_len tokens.
    Recomputation is not counted. The chunked form the program runs
    does more operations than the linear recurrence; those are not
    model operations.
    """
    d = cfg["d_model"]
    e = cfg["expand"] * d
    n = cfg["d_state"]
    p = cfg["headdim"]
    g = cfg["ngroups"]
    h = e // p
    w = cfg["d_conv"]
    per_layer = (2.0 * d * (2 * e + 2 * g * n + h)
                 + 2.0 * w * (e + 2 * g * n)
                 + 5.0 * h * p * n + 2.0 * h * p
                 + 2.0 * e * d)
    return 3.0 * cfg["n_layer"] * per_layer + 3.0 * 2.0 * d / seq_len
