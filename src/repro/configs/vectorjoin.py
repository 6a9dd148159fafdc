"""Vector-join operator configs (the paper's contribution as a first-class
framework feature).

Presets name the paper's §5.1.2 baselines; ``JOIN_DRYRUN_CELLS`` defines the
distributed-join dry-run cells recorded alongside the 40 model cells
(X replicated per shard, Y sharded over the data axes — DESIGN §2.7).
"""
from __future__ import annotations

import dataclasses

from repro.core.types import JoinConfig, TraversalConfig

# paper §5.1.2 method presets (ES patience 10, L=256 defaults of [38])
PRESETS = {
    "nlj": JoinConfig(method="nlj"),
    "index": JoinConfig(method="index"),
    "es": JoinConfig(method="es"),
    "es_hws": JoinConfig(method="es_hws"),          # == SIMJOIN
    "es_sws": JoinConfig(method="es_sws"),
    "es_mi": JoinConfig(method="es_mi"),
    "es_mi_adapt": JoinConfig(method="es_mi_adapt"),
}


def preset(name: str, *, theta: float, **tcfg_kw) -> JoinConfig:
    cfg = PRESETS[name]
    tr = dataclasses.replace(cfg.traversal, **tcfg_kw) if tcfg_kw \
        else cfg.traversal
    return dataclasses.replace(cfg, theta=theta, traversal=tr)


# ---------------------------------------------------------------------------
# engine presets — how a serving deployment instantiates JoinEngine
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class EngineSpec:
    """Constructor recipe for a ``repro.engine.JoinEngine`` deployment.

    ``n_shards=0`` means "one shard per visible device" (resolved at
    ``make_engine`` time); 1 pins single-device execution.

    ``quant`` selects the deployment's compressed-storage mode
    (``core.types.QUANT_MODES``): it names the ``FilterCascade`` tier
    chain (``quant.TIERS_BY_MODE``) every join served by the engine
    defaults to — ``"sq8"`` filters on certified int8 bounds + exact f32
    re-rank, ``"sketch8"`` adds the 1-bit sketch tier above int8
    (progressive refinement: Hamming bounds prune first, int8 confirms,
    f32 re-ranks the band) — with tier stores cached per index artifact
    (and per shard).

    ``quant_build`` drives the *offline* index builds through the same
    cascade (``graph.build_index(quant=...)``): the kNN sweep and RNG
    prune run on certified bounds, f32 only for the ambiguous band —
    neighbor lists are identical to the f32 build.

    ``metric`` (``core.types.METRICS``) belongs to the table and its
    index, as a vector database collection's does: every join the engine
    serves is judged in it. ``"ip"`` runs with ``quant`` and
    ``quant_build`` ``"off"`` on one device; other combinations raise.
    """
    k: int = 48                    # kNN candidates per node at build time
    degree: int = 32               # index max out-degree R
    style: str = "nsg"
    n_shards: int = 1
    carry_window: int = 4096       # streaming work-sharing donor window
    max_cached_indexes: int = 4    # per-X artifact LRU capacity
    quant: str = "off"             # storage mode (off | sq8 | sketch8)
    quant_build: str = "off"       # cascade-driven index builds
    metric: str = "l2"             # the table's space (l2 | ip)

    def build_kw(self) -> dict:
        kw = dict(k=self.k, degree=self.degree, style=self.style)
        if self.quant_build != "off":
            kw["quant"] = self.quant_build
        return kw


ENGINE_PRESETS = {
    # single-device defaults matching the paper's offline build
    "default": EngineSpec(),
    # CI-scale: smaller graphs, fast builds
    "ci": EngineSpec(k=32, degree=24),
    # serving: data side sharded over every visible device
    "serving": EngineSpec(n_shards=0, carry_window=16_384,
                          max_cached_indexes=8),
    # serving with compressed storage: ~4× more vectors resident per
    # shard, distance filtering on int8 with exact re-rank; offline
    # builds run through the same cascade (identical edges, f32 build
    # traffic cut to the ambiguous band)
    "serving_sq8": EngineSpec(n_shards=0, carry_window=16_384,
                              max_cached_indexes=8, quant="sq8",
                              quant_build="sq8"),
    # serving with the full progressive-refinement cascade: 1-bit sketch
    # prune → int8 confirm → f32 re-rank (cheapest bytes/candidate at
    # d ≥ 256)
    "serving_sketch8": EngineSpec(n_shards=0, carry_window=16_384,
                                  max_cached_indexes=8, quant="sketch8",
                                  quant_build="sq8"),
    # inner-product table (cross-modal embeddings, MIPS-style joins):
    # the default build and one device, quant off
    "ip": EngineSpec(metric="ip"),
}


def make_engine(Y, spec: str | EngineSpec = "default", *,
                default: JoinConfig | None = None, **overrides):
    """Instantiate a ``JoinEngine`` from a named (or explicit) spec."""
    import jax

    from repro.engine import JoinEngine

    if isinstance(spec, str):
        spec = ENGINE_PRESETS[spec]
    if overrides:
        spec = dataclasses.replace(spec, **overrides)
    n_shards = spec.n_shards or len(jax.devices())
    if spec.quant != "off":
        default = dataclasses.replace(default or JoinConfig(),
                                      quant=spec.quant)
    return JoinEngine(Y, build_kw=spec.build_kw(), default=default,
                      n_shards=n_shards, carry_window=spec.carry_window,
                      max_cached_indexes=spec.max_cached_indexes,
                      metric=spec.metric)


@dataclasses.dataclass(frozen=True)
class JoinCell:
    """One distributed-join dry-run cell.

    max_iters bounds the traversal while-loop; for the roofline it is set
    to the *expected* per-wave iteration count (the production safety
    bound of 4096 would make the static cost model 100× pessimistic —
    measured CI waves converge in ≲64 iterations). dtype bf16 halves the
    gather traffic of the distance hot-spot (beyond-paper; §Perf).
    """
    name: str
    n_query: int
    n_data: int          # global |Y| (sharded over data axes)
    dim: int
    degree: int          # index max out-degree R
    wave_size: int
    pool_cap: int
    hybrid: bool = False
    max_iters: int = 64
    dtype: str = "float32"
    # traversal loops exit data-dependently, so the static HLO cost model
    # sees one iteration; the dry-run scales by this measured expectation
    # (es_mi on CI data: ~3 iters/wave at θ1, ~52 at θ4)
    expected_iters: int = 32


JOIN_DRYRUN_CELLS = (
    # embedding-scale joins: |Y| per shard × 256/512 shards ⇒ 0.1–1B rows
    JoinCell("join_sift_like", 10_000, 524_288, 128, 32, 256, 512),
    JoinCell("join_clip_like", 10_000, 524_288, 512, 32, 256, 512),
    JoinCell("join_ood_hybrid", 10_000, 262_144, 512, 32, 256, 512,
             hybrid=True),
    JoinCell("join_lm_embed", 4_096, 1_048_576, 2048, 32, 256, 256),
    # §Perf iteration: bf16 vectors (distances still f32-accumulated)
    JoinCell("join_lm_embed_bf16", 4_096, 1_048_576, 2048, 32, 256, 256,
             dtype="bfloat16"),
)
