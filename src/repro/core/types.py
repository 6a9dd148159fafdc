"""Shared types for the vector-join core."""
from __future__ import annotations

import dataclasses
import os
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

# Sentinel for "no neighbor" slots in padded neighbor tables.
NO_NODE = -1


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class GraphIndex:
    """A graph-based ANN index in TPU-friendly dense form.

    The adjacency is a padded neighbor table (the TPU analogue of NSG's
    adjacency lists). ``mean_nbr_dist`` is the paper's §4.5 side table (one
    f32 per node, <1% overhead) used by the OOD predictor.
    """
    vecs: Array                 # (N, d) node vectors
    nbrs: Array                 # (N, R) int32 neighbor ids, NO_NODE padded
    start: Array                # () int32 navigating node (medoid)
    mean_nbr_dist: Array        # (N,) f32 mean L2 distance to neighbors
    n_data: int = dataclasses.field(metadata=dict(static=True))
    # Nodes with id < n_data are data points (Y). For a merged index
    # G_{X∪Y}, ids in [n_data, N) are query nodes; for a plain data index,
    # n_data == N.

    @property
    def n_nodes(self) -> int:
        return self.vecs.shape[0]

    @property
    def degree(self) -> int:
        return self.nbrs.shape[1]

    def is_data(self, ids: Array) -> Array:
        return (ids >= 0) & (ids < self.n_data)


@dataclasses.dataclass(frozen=True)
class TraversalConfig:
    """Knobs for the batched traversal engine (paper Alg. 2 & 4).

    beam_width       — L, the greedy-phase queue size (paper default 256).
    expand_per_iter  — E, beam entries expanded per loop iteration (E=1 is
                       the paper's sequential best-first; larger E trades
                       faithfulness of the *work metric* for throughput;
                       result semantics are unchanged).
    patience         — ES plateau iterations (paper: 10); <0 disables ES
                       (the INDEX baseline).
    pool_cap         — C, capacity of the in-range result pool per query
                       (the paper's unbounded BFS queue; overflow counted).
    hybrid_beam      — L for the BBFS out-range queue (paper Alg. 4);
                       0 = plain BFS.
    hybrid_patience  — BBFS early-stop plateau (paper: 1).
    hybrid_guard     — eviction-protection radius for the BBFS out-range
                       beam under quantized modes, as a multiple of θ²:
                       entries whose *certified upper bound* is below
                       ``hybrid_guard · θ²`` cannot be evicted ahead of
                       unprotected entries (the OOD recall floor; ≤ 0
                       disables, exact f32 is unaffected either way).
    seeds_max        — max seeds probed per query (caps HWS parent caches).
    max_iters        — hard bound on loop iterations (safety net).
    rerank_cap       — initial capacity of the band-compacted exact
                       re-rank (quantized modes): pooled ambiguous-band
                       entries are stably compacted device-side into this
                       many slots before the f32 gather kernel runs, so
                       re-rank traffic scales with band occupancy instead
                       of ``pool_cap``. Waves whose band overflows the
                       capacity are transparently re-ranked at the next
                       power-of-two capacity (sticky per runner) — the
                       emitted pair set never depends on the cap. ≤ 0
                       disables compaction (full ``pool_cap`` width).
    early_exit       — PDX modes (``pdx8``/``sketchpdx8``): retire
                       candidate lanes mid-vector once the slab-partial
                       sum plus the certified remaining-dims bound
                       exceeds the threshold (see ``quant/pdx.py``).
                       Retirement is certified, so the emitted pair set
                       is provably identical on/off; off exists for
                       bisection and as the wall-clock baseline. The
                       REPRO_EARLY_EXIT env var overrides at run time.
                       Ignored by non-PDX modes.
    """
    beam_width: int = 256
    expand_per_iter: int = 4
    patience: int = 10
    pool_cap: int = 1024
    hybrid_beam: int = 64
    hybrid_patience: int = 1
    hybrid_guard: float = 4.0
    seeds_max: int = 16
    max_iters: int = 4096
    rerank_cap: int = 128
    early_exit: bool = True
    dist_impl: str | None = None   # kernels.ops impl override


def env_flag(name: str, default: bool) -> bool:
    """Boolean env-var override with an *empty-counts-as-unset* contract:
    an unset or empty/whitespace value returns ``default``, anything else
    is truthy unless it spells one of ``0/off/false/no`` (case- and
    whitespace-insensitive). The empty-string rule lets CI matrices
    template a variable per leg (``REPRO_OVERLAP: ''`` on non-off legs)
    without pinning every config to the enabled path.

    The single owner of the flag grammar — ``early_exit_enabled``,
    ``engine.waves.overlap_enabled``, and the ``REPRO_SERVE_*`` serving
    knobs (``serve.join_service``) all parse through here."""
    env = os.environ.get(name)
    if env is not None and env.strip():
        return env.strip().lower() not in ("0", "off", "false", "no")
    return default


def early_exit_enabled(tcfg: TraversalConfig) -> bool:
    """``tcfg.early_exit``, unless the ``REPRO_EARLY_EXIT`` env var
    overrides it (CI bisection: ``REPRO_EARLY_EXIT=off`` forces the
    full-scan PDX kernels everywhere without touching configs).
    Mirrors ``engine.waves.overlap_enabled``."""
    return env_flag("REPRO_EARLY_EXIT", tcfg.early_exit)


METHODS = ("nlj", "index", "es", "es_hws", "es_sws", "es_mi", "es_mi_adapt")

# Compressed-storage modes: "off" streams f32 vectors through the distance
# kernels; "sq8" runs traversal/threshold filtering on QuantStore int8
# codes against certified lower bounds and re-ranks survivors with the
# exact f32 kernel (emitted pairs are identical — see quant/store.py);
# "sketch8" adds the 1-bit SketchStore tier above sq8 (progressive
# refinement: Hamming-sketch bounds prune first, int8 confirms survivors,
# f32 re-ranks the band — see quant/sketch.py); "pdx8" swaps the int8
# tier for the dimension-partitioned PdxTier whose kernels early-exit
# mid-vector on certified tail bounds (see quant/pdx.py); "sketchpdx8"
# stacks the 1-bit sketch above it.
QUANT_MODES = ("off", "sq8", "sketch8", "pdx8", "sketchpdx8")

# Modes that route traversal through certified-lower-bound filtering.
QUANT_FILTER_MODES = ("sq8", "sketch8", "pdx8", "sketchpdx8")


@dataclasses.dataclass(frozen=True)
class JoinConfig:
    method: str = "es_mi_adapt"
    theta: float = 1.0
    traversal: TraversalConfig = dataclasses.field(default_factory=TraversalConfig)
    wave_size: int = 256           # queries processed per batched wave
    ood_factor: float = 1.5        # paper §4.5 d1 > 1.5 * d2
    quant: str = "off"             # compressed-storage mode (QUANT_MODES)
    # Two-stage wave pipeline: while the device traverses wave k+1, the
    # host assembles wave k's pairs and work-sharing cache (the next wave
    # is launched from a small seed-feedback transfer alone). Off ⇒ the
    # fully sequential loop; pair sets and cache contents are identical
    # either way. The REPRO_OVERLAP env var overrides this at run time
    # (CI bisection escape hatch).
    overlap: bool = True

    def __post_init__(self):
        if self.method not in METHODS:
            raise ValueError(f"unknown method {self.method!r}; one of {METHODS}")
        if self.quant not in QUANT_MODES:
            raise ValueError(
                f"unknown quant mode {self.quant!r}; one of {QUANT_MODES}")


@dataclasses.dataclass
class JoinStats:
    n_dist: int = 0                # distance computations (paper's C4 metric)
    n_iters: int = 0               # traversal loop iterations
    n_slots: int = 0               # candidate slots the traversal probes
    #                                evaluated: B × K per probe, slots
    #                                masked by the visited test, the dedup
    #                                or an inactive lane included (n_dist
    #                                counts the kept ones)
    n_lane_iters: int = 0          # loop iterations in which each valid
    #                                lane was still active, summed over
    #                                lanes (wave_size × n_iters bounds it)
    n_overflow: int = 0            # in-range pool overflow (missed results)
    greedy_seconds: float = 0.0
    expand_seconds: float = 0.0    # BFS / BBFS phase
    other_seconds: float = 0.0     # ordering, caching, assembly
    n_ood: int = 0                 # queries predicted OOD (adapt only)
    peak_cache_entries: int = 0    # work-sharing cache footprint
    n_rerank: int = 0              # exact f32 re-rank evaluations (sq8 mode;
    #                                n_dist counts quantized filter dists)
    quant_bytes: int = 0           # bytes resident for QuantStore artifacts
    n_esc8: int = 0                # sketch8 only: candidates escalated from
    #                                the 1-bit sketch tier to int8 (n_dist
    #                                counts sketch-tier probes; the sketch
    #                                pruned n_dist - n_esc8 before any int8
    #                                work)
    wait_seconds: float = 0.0      # pipelined runs: host blocked on the
    #                                device (seed-feedback fetch); the
    #                                sequential path reports its device
    #                                time under greedy/expand instead
    n_rerank_gather: int = 0       # f32 rows dispatched to the re-rank
    #                                gather kernel — with band compaction
    #                                this is lanes × capacity (sized to
    #                                band occupancy), not lanes × pool_cap
    band_occ_per_shard: tuple = () # sharded path: ambiguous-band entries
    #                                re-ranked per shard (aligned with
    #                                shard ids; sums to n_rerank)
    n_dims_scanned: int = 0        # PDX modes: dimensions actually scanned
    #                                by early-exit kernels, summed over
    #                                candidate lanes (retired lanes count
    #                                only the slabs they saw)
    n_dims_total: int = 0          # PDX modes: lanes × full dim — the
    #                                denominator of dims_scanned_frac
    # Work-sharing cache effectiveness (the paper's core claim; see
    # waves.seeds_from_cache / update_sws_cache / engine._remember):
    cache_hits: int = 0            # lanes seeded from a parent's entry
    cache_misses: int = 0          # lanes whose parent had no usable
    #                                entry (fell back to s_Y)
    cache_evictions: int = 0       # entries dropped (carry-window
    #                                eviction or overwrite)
    cache_tombstones: int = 0      # pipelined eviction-vs-pending races
    #                                resolved by dropping the entry after
    #                                its late write (engine drain)
    # Bytes moved per transfer class of the wave pipeline (device↔host
    # accounting; ARCHITECTURE §6):
    bytes_feedback: int = 0        # seed-feedback + band-occupancy
    #                                fetches (the small blocking
    #                                inter-wave transfer)
    bytes_band: int = 0            # f32 rows dispatched to the
    #                                band-compacted re-rank gather
    #                                (n_rerank_gather × d × 4)
    bytes_assembly: int = 0        # the bulky per-wave pool transfer
    #                                (idx/dist/keep/stats block)
    # Bytes moved per *collective* on the sharded mesh (device↔device
    # accounting; ARCHITECTURE §8). Each transfer class is routed over
    # one collective — these meters are how the routing table is
    # observable:
    bytes_allgather: int = 0       # all_gather pool combine: per-device
    #                                payload received from peers during
    #                                the on-device pair-pool merge
    bytes_ppermute: int = 0        # ppermute ring combine (the same
    #                                merge routed as S−1 ring shifts for
    #                                large shard groups)
    bytes_psum: int = 0            # psum partial-sum combines (hybrid
    #                                dimension-partitioned distances)
    overflow_retries: int = 0      # grow-and-retry rounds taken by the
    #                                band/merge capacity controls
    #                                (RerankCap/StickyCap) — each retry
    #                                re-dispatches a wave at the next
    #                                power-of-two cap, so a well-seeded
    #                                estimate keeps this at 0

    @property
    def total_seconds(self) -> float:
        return (self.greedy_seconds + self.expand_seconds
                + self.other_seconds + self.wait_seconds)

    @property
    def dims_scanned_frac(self) -> float:
        """Mean fraction of dimensions scanned per candidate lane by the
        PDX early-exit kernels (1.0 when early exit is off, no PDX tier
        ran, or no lanes were scanned)."""
        if self.n_dims_total <= 0:
            return 1.0
        return self.n_dims_scanned / self.n_dims_total

    def as_dict(self) -> dict[str, Any]:
        return dict(dataclasses.asdict(self), total_seconds=self.total_seconds,
                    dims_scanned_frac=self.dims_scanned_frac)

    # -- merge / metrics-registry bridge (obs/) -----------------------------

    # Non-additive fields. Everything else merges by summation, so new
    # counters are merge-covered by default; a field with different
    # semantics must be registered here (test_obs asserts every field is
    # classified).
    _MERGE_MAX = ("peak_cache_entries",)   # high-water marks
    _MERGE_CAT = ("band_occ_per_shard",)   # per-shard listings: merging
    #                                        disjoint shard groups
    #                                        concatenates them

    def merge(self, other: "JoinStats") -> "JoinStats":
        """Associative, field-complete combine of two disjoint pieces of
        work (shards, waves, streamed batches): counters and seconds
        sum, high-water marks take the max, per-shard tuples
        concatenate. Replaces the ad-hoc per-field summing the sharded
        path used to do — ``core/distributed.py`` builds one ``JoinStats``
        per shard and reduces with ``merge``."""
        kw: dict[str, Any] = {}
        for f in dataclasses.fields(self):
            a, b = getattr(self, f.name), getattr(other, f.name)
            if f.name in self._MERGE_MAX:
                kw[f.name] = max(a, b)
            elif f.name in self._MERGE_CAT:
                kw[f.name] = tuple(a) + tuple(b)
            else:
                kw[f.name] = a + b
        return JoinStats(**kw)

    def publish(self, metrics, prefix: str = "join") -> None:
        """Accumulate this join's stats into an ``obs.Metrics`` registry
        (the engine-lifetime backend): additive fields increment
        counters, high-water marks drive ``set_max`` gauges, and the
        per-shard band listing lands as per-shard gauges plus a
        max/mean imbalance gauge."""
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            name = f"{prefix}.{f.name}"
            if f.name in self._MERGE_MAX:
                metrics.gauge(name).set_max(v)
            elif f.name in self._MERGE_CAT:
                for i, b in enumerate(v):
                    metrics.gauge(f"{name}.shard{i}").set(int(b))
                if v:
                    mean = sum(v) / len(v)
                    metrics.gauge(f"{prefix}.shard_band_imbalance").set(
                        max(v) / mean if mean > 0 else 1.0)
            elif v:
                metrics.counter(name).inc(v)

    @classmethod
    def from_metrics(cls, metrics, prefix: str = "join") -> "JoinStats":
        """Materialize the registry's cumulative ``{prefix}.*`` values
        back into a ``JoinStats`` — the engine-lifetime aggregate is the
        same public dataclass every single join reports."""
        kw: dict[str, Any] = {}
        for f in dataclasses.fields(cls):
            name = f"{prefix}.{f.name}"
            if f.name in cls._MERGE_CAT:
                vals = []
                while metrics.get(f"{name}.shard{len(vals)}") is not None:
                    vals.append(int(metrics.value(f"{name}.shard{len(vals)}")))
                kw[f.name] = tuple(vals)
            else:
                v = metrics.value(name, 0)
                kw[f.name] = float(v) if f.type == "float" else int(v)
        return cls(**kw)


@dataclasses.dataclass
class JoinResult:
    """Join output: pairs[i] = (query_id, data_id)."""
    pairs: np.ndarray              # (P, 2) int64
    stats: JoinStats

    def pair_set(self) -> set[tuple[int, int]]:
        return set(map(tuple, self.pairs.tolist()))


def recall(result: JoinResult, truth_pairs: np.ndarray) -> float:
    """Global recall vs ground-truth pair array (paper §2.1)."""
    if len(truth_pairs) == 0:
        return 1.0
    found = result.pair_set()
    truth = set(map(tuple, np.asarray(truth_pairs).tolist()))
    return len(found & truth) / len(truth)
