"""Paper Fig. 12: latency breakdown — greedy search vs BFS/BBFS vs other.

Also the compressed-storage comparison: ``run_quant`` reruns methods with
``quant ∈ {off, sq8, sketch8, pdx8, sketchpdx8}`` on a high-dim (d ≥ 256)
dataset — each
mode names a ``FilterCascade`` tier chain (``quant.TIERS_BY_MODE``) the
engine assembles per index artifact — and reports the per-tier split of
distance work and bytes moved per emitted pair (``common.dist_bytes`` —
d×4 bytes per f32 distance, d×1 per int8 filter distance, d/8 +
slack-table bytes per 1-bit sketch probe, d×4 per exact re-rank). For
``sketch8`` the per-tier survivor counts are the cascade's shape:
``n_dist`` sketch probes → ``n_esc8`` int8 escalations (``sketch_prune``
= the fraction the sketch tier pruned before any int8 work; ≥ 50% on the
NLJ prefilter at d ≥ 256 at the tight thresholds) → ``n_rerank`` f32
evaluations. The *offline* half of the story — the cascade driving the
index build itself — is ``bench_offline.py``.
"""
from __future__ import annotations

from benchmarks.common import (SCALES, dist_bytes, emit, run_method,
                               theta_grid)

METHODS = ("index", "es", "es_hws", "es_sws", "es_mi", "es_mi_adapt")
QUANT_METHODS = ("nlj", "es", "es_mi", "es_mi_adapt")
QUANT_MODES = ("off", "sq8", "sketch8", "pdx8", "sketchpdx8")


def run(scale: str = "ci", *, regime: str = "manifold",
        theta_idxs=(1, 4, 7)) -> list[dict]:
    rows = []
    grid = theta_grid(regime, scale)
    for ti in theta_idxs:
        theta = grid[ti - 1]
        for method in METHODS:
            # per-phase timing needs the sequential path: the pipelined
            # loop never blocks between greedy and expand, so their split
            # is unobservable there (bench_overall reports the pipelined
            # wall-clock instead)
            res, dt, rec = run_method(regime, method, theta, scale=scale,
                                      overlap=False)
            s = res.stats
            rows.append(dict(
                dataset=regime, theta_idx=ti, method=method,
                greedy_s=s.greedy_seconds, expand_s=s.expand_seconds,
                other_s=s.other_seconds, total_s=s.total_seconds,
                recall=rec))
    return rows


def run_quant(scale: str = "ci_hd", *, regime: str = "manifold",
              theta_idxs=(1, 2), methods=QUANT_METHODS,
              modes=QUANT_MODES) -> list[dict]:
    """f32 vs sq8 vs sketch8 on a d≥256 dataset: per-tier survivor
    counts, kernel seconds and bytes moved."""
    dim = SCALES[scale]["dim"]
    rows = []
    grid = theta_grid(regime, scale)
    for ti in theta_idxs:
        theta = grid[ti - 1]
        for method in methods:
            base_bytes = None
            for quant in modes:
                res, dt, rec = run_method(regime, method, theta,
                                          scale=scale, quant=quant,
                                          overlap=False)
                s = res.stats
                nbytes = dist_bytes(res, dim, quant)
                if quant == "off":
                    base_bytes = nbytes
                rows.append(dict(
                    dataset=regime, dim=dim, theta_idx=ti, method=method,
                    quant=quant, greedy_s=s.greedy_seconds,
                    expand_s=s.expand_seconds, other_s=s.other_seconds,
                    total_s=s.total_seconds, n_dist=s.n_dist,
                    n_esc8=s.n_esc8,
                    sketch_prune=(1.0 - s.n_esc8 / max(s.n_dist, 1)
                                  if quant == "sketch8" else 0.0),
                    n_rerank=s.n_rerank,
                    # PDX early exit: fraction of candidate dimensions
                    # the slab kernels actually scanned (1.0 elsewhere)
                    dims_scanned_frac=s.dims_scanned_frac,
                    dist_bytes=nbytes,
                    # NaN, not 1.0, when the caller skipped the f32 leg:
                    # a fake unity ratio would read as "same bytes as f32"
                    bytes_vs_f32=(nbytes / max(base_bytes, 1)
                                  if base_bytes is not None
                                  else float("nan")),
                    bytes_per_pair=nbytes / max(len(res.pairs), 1),
                    recall=rec))
    return rows


def main(scale: str = "ci") -> None:
    emit(run(scale))
    # a separate section: a different schema than the breakdown above
    print("\n# quant: per-tier distance work, bytes, and dims scanned — "
          "f32 vs sq8 vs sketch8 vs pdx8 vs sketchpdx8 (d >= 256)")
    emit(run_quant("full_hd" if scale == "full" else "ci_hd"))


if __name__ == "__main__":
    main()
