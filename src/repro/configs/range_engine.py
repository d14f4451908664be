"""range-engine — the paper's own system as a config (11th, bonus row).

A production range-retrieval deployment: corpus sharded over the model axis
(one Vamana sub-index per shard), query batches sharded over data — each
query carrying its *own* radius (the radii vector shards with the batch;
serving traffic mixes duplicate-detection-tight and recommendation-wide
thresholds in one micro-batch) — fused single-program search
(beam -> greedy) per cell, union merge. The dry-run lowers the shard_map
program on the 256/512-chip meshes — proving the paper's system itself
distributes, not just the ML architectures around it.
"""
import dataclasses

from ..core.beam_search import SearchConfig
from ..core.range_search import RangeConfig
from ..dist.sharding import Rule
from ..optim.adamw import AdamWConfig
from .common import ArchSpec, ShapeSpec


@dataclasses.dataclass(frozen=True)
class EngineDeployConfig:
    name: str = "range-engine"
    shard_corpus: int = 1_000_000     # points per model-axis shard
    dim: int = 128
    max_degree: int = 32
    metric: str = "l2"
    # corpus storage dtype. "int8" is the production setting for
    # billion-point shards: each shard quantizes locally (core.corpus) and
    # the query path runs the two-pass pipeline — guard-banded approximate
    # search on int8 codes (d + 12 hot bytes/vector vs 4d for f32), exact
    # f32 rerank of the radius boundary band only. (The earlier §Perf C
    # bf16 note still holds for the XLA path: a bare storage cast without
    # the fused kernels *raised* the memory term 1.4x; the int8 pipeline
    # avoids that by dequantizing in-register in both the XLA reference and
    # the Pallas int8 kernels.) Kept f32 here so the dry-run baseline stays
    # comparable across PRs; flip via replace() for the quantized deploy.
    corpus_dtype: str = "float32"
    range_cfg: RangeConfig = dataclasses.field(default_factory=lambda: RangeConfig(
        search=SearchConfig(beam=64, max_beam=64, visit_cap=256,
                            # multi-node frontier expansion (the XLA path;
                            # use_expand_kernel stays off: which path a
                            # deployment should run is not measured yet)
                            expand_width=4),
        mode="greedy", result_cap=1024, frontier_rounds=2048))

    def __post_init__(self):
        # keep the declarative SearchConfig knob in lockstep with the
        # deploy-level one (engine cells and builders consult either; the
        # server validates it against the corpus it actually serves). The
        # non-default side wins, so setting EITHER knob to "int8"/"bfloat16"
        # propagates; setting both to conflicting non-defaults is an error,
        # never a silent override.
        s = self.range_cfg.search.corpus_dtype
        if s != self.corpus_dtype:
            if s != "float32" and self.corpus_dtype != "float32":
                raise ValueError(
                    f"corpus_dtype={self.corpus_dtype!r} conflicts with "
                    f"range_cfg.search.corpus_dtype={s!r}")
            unified = s if self.corpus_dtype == "float32" else self.corpus_dtype
            object.__setattr__(self, "corpus_dtype", unified)
            object.__setattr__(self, "range_cfg", dataclasses.replace(
                self.range_cfg, search=dataclasses.replace(
                    self.range_cfg.search, corpus_dtype=unified)))

    def overrides(self, **kw) -> "EngineDeployConfig":
        """One explicit merge point for deploy-time knob changes.

        Each keyword is routed to the level that owns it — an
        ``EngineDeployConfig`` field, a ``RangeConfig`` field, or a
        ``SearchConfig`` field — and a new config is returned with
        everything else untouched. This replaces the scattered ad-hoc
        ``dataclasses.replace`` chains (and the deprecated
        ``ServerConfig.expand_width`` side channel): the deploy config is
        the single source of truth for what the engine serves with.

        Keys owned by two levels resolve top-down (deploy > range >
        search): ``lam`` sets the RangeConfig phase-2 trigger, and the
        cross-level contracts propagate — ``metric`` sets both the deploy
        field and ``search.metric``; ``corpus_dtype`` sets the deploy field
        and ``__post_init__`` syncs it into the search config. Unknown keys
        raise ``TypeError`` (a typo'd override must never silently no-op).
        """
        deploy_f = {f.name for f in dataclasses.fields(EngineDeployConfig)}
        range_f = {f.name for f in dataclasses.fields(RangeConfig)} - {"search"}
        search_f = {f.name for f in dataclasses.fields(SearchConfig)}
        d_kw, r_kw, s_kw = {}, {}, {}
        for k, v in kw.items():
            if k in deploy_f:
                d_kw[k] = v
                if k == "metric":
                    s_kw[k] = v
                if k == "corpus_dtype":
                    s_kw[k] = v  # keep both sides of the post_init contract
            elif k in range_f:
                r_kw[k] = v
            elif k in search_f:
                s_kw[k] = v
            else:
                raise TypeError(f"overrides() got unknown knob {k!r}")
        rc = d_kw.pop("range_cfg", self.range_cfg)
        if s_kw:
            rc = dataclasses.replace(rc, search=dataclasses.replace(
                rc.search, **s_kw))
        if r_kw:
            rc = dataclasses.replace(rc, **r_kw)
        return dataclasses.replace(self, range_cfg=rc, **d_kw)


def reduced() -> EngineDeployConfig:
    return EngineDeployConfig(
        name="range-engine-smoke", shard_corpus=2_000, dim=16, max_degree=8,
        range_cfg=RangeConfig(search=SearchConfig(beam=16, max_beam=16,
                                                  visit_cap=64,
                                                  expand_width=4),
                              mode="greedy", result_cap=128,
                              frontier_rounds=256))


ARCH = ArchSpec(
    arch_id="range-engine",
    family="engine",
    model_cfg=EngineDeployConfig(),
    shapes={
        "search_4k": ShapeSpec("search_4k", "range_search", global_batch=4096,
                               notes="batched online range queries"),
        "search_64k": ShapeSpec("search_64k", "range_search",
                                global_batch=65_536,
                                notes="bulk range search (Szilvasy-style)"),
    },
    rules=[Rule(r".*", ())],
    opt_cfg=AdamWConfig(),
    source="this paper",
    technique_note="the paper's contribution itself",
    reduced=reduced,
)
