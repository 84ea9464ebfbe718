"""graftlint configuration: scopes, doc locations, and the jit allowlist.

Everything here is overridable per-``LintConfig`` so the fixture tests can
point the rules at synthetic trees (tests/fixtures/graftlint/)."""
from __future__ import annotations

from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Tuple

# Paths (repo-root-relative, posix) whose env reads are treated as
# trace-time for policy-key-coverage: these trees hold the op/policy gates
# that execute under jax tracing, so an MXTPU_* read here is baked into
# compiled executables unless it is in registry.policy_key (or explicitly
# suppressed as host-side at the read site).
DEFAULT_TRACE_SCOPES: Tuple[str, ...] = (
    "mxtpu/ops",
    "mxtpu/contrib",
    "mxtpu/parallel",
    "mxtpu/resilience.py",
)

DEFAULT_POLICY_KEY_MODULE = "mxtpu/ops/registry.py"
DEFAULT_ENV_DOC = "docs/env_vars.md"
DEFAULT_METRIC_DOC = "docs/observability.md"

# Trees whose telemetry writer calls feed metric-name-catalog: the
# runtime package is the metric namespace the catalog documents (bench /
# tools consume metrics, they do not declare new names).
DEFAULT_METRIC_SCOPES: Tuple[str, ...] = ("mxtpu",)

# Extra roots scanned (read-only) by env-var-catalog beyond the CLI paths:
# docs/env_vars.md is a repo-global catalog, so BENCH_* rows read only by
# the bench/tooling layer must not look stale when linting mxtpu/ alone.
DEFAULT_ENV_EXTRA_ROOTS: Tuple[str, ...] = ("bench.py", "tools", "tests")

# Never analyzed / never scanned: the lint fixtures are deliberately-bad
# code, and would otherwise convict themselves in the self-clean gate.
DEFAULT_EXCLUDE: Tuple[str, ...] = ("tests/fixtures/graftlint",)

# Trees where every jax.jit site must resolve through the compile
# service (mxtpu/compile_service.py): a registered-but-out-of-band cache
# here is a finding — it would miss the LRU bound, the persistent
# executable cache, and AOT warmup. Fixture trees (paths outside these
# prefixes) keep exercising the plain record_retrace discipline.
DEFAULT_SERVICE_SCOPES: Tuple[str, ...] = ("mxtpu/",)

# retrace-site-registration allowlist: (repo-relative file, enclosing
# function of the jax.jit call) -> entry. An entry declares WHERE the
# site's compiles are actually counted and what its cache key is, so the
# jit-surface inventory stays complete even for sites whose
# record_retrace lives in a caller.
JIT_ALLOWLIST: Dict[Tuple[str, str], Dict[str, str]] = {
    ("mxtpu/optimizer_fused.py", "_build"): {
        "site": "fused_optimizer",
        "service": True,
        "reason": "FusedUpdater._cached_jit is the single cache front door "
                  "for this builder; every executable-cache miss resolves "
                  "through compile_service.get_or_build (canonical key, "
                  "retrace reporting, LRU, persistent disk cache) before "
                  "invoking _build",
        "cache_key": "(optimizer class, static config, per-param specs "
                     "incl. sharding tokens, MeshPlan fingerprint) + "
                     "registry.policy_key — FusedUpdater._cached_jit; the "
                     "mesh-native Trainer shares this cache",
    },
    ("mxtpu/parallel/train.py", "_jitted"): {
        "site": "parallel.train_step",
        "service": True,
        "reason": "ShardedTrainStep._jitted only BUILDS the step's jit "
                  "(``lowered()`` lowers the same function anew for the "
                  "tools that compare programs); the cache front door is "
                  "ShardedTrainStep._build, which resolves every miss "
                  "through compile_service.get_or_build, ahead of time",
        "cache_key": "canonical_key(site='parallel.train_step', ...) in "
                     "ShardedTrainStep._build: batch structure and shapes, "
                     "optimizer rule, parameter shapes, shardings, "
                     "donation, mesh + registry.policy_key",
    },
    ("mxtpu/serving/engine.py", "_build_for"): {
        "site": "serving.predict",
        "service": True,
        "reason": "Predictor._build_for only BUILDS the bucket jit; the "
                  "cache front door is Predictor._get_jit / "
                  "warmup_entries, which resolve every miss through "
                  "compile_service.get_or_build with a canonical key at "
                  "site self._site (per-INSTANCE, so each ReplicaSet "
                  "member gets its own watchdog site "
                  "serving.predict.r<i>) and group-dedup identical "
                  "replica lowerings — the static rule sees no seam in "
                  "the build closure and this entry declares it",
        "cache_key": "(bucket padded shapes+dtypes) + registry.policy_key "
                     "— one executable cache per Predictor instance; "
                     "per-replica caches (sites serving.predict.r<i>, "
                     "mxtpu/serving/replicas.py) are each bounded by "
                     "#buckets, total compiles <= buckets x replicas; "
                     "elastic members (ReplicaSet.add_replica — scale-up "
                     "and dead-replica replacement) extend the same "
                     "family with fresh never-reused indices, warmed "
                     "AOT before joining dispatch",
    },
    ("mxtpu/serving/decode.py", "_build_jit"): {
        "site": "serving.decode",
        "reason": "DecodeEngine._build_jit is the single compile front "
                  "door for the decode cache (step executables per cohort "
                  "capacity bucket + insert executables per prefill seq "
                  "bucket, and in paged mode the verify/extend family "
                  "over the same buckets); it calls "
                  "telemetry.record_retrace(self._site, "
                  "...) on every miss before jax.jit — the site name is "
                  "per-INSTANCE (default serving.decode) so the static "
                  "rule sees '<dynamic>' and this entry declares the base "
                  "site for the inventory",
        "cache_key": "(kind step|insert|verify|extend, "
                     "cohort-capacity-or-seq bucket, int8 flag, "
                     "page_tokens, pool_pages, spec_k, draft kv layout) "
                     "+ registry.policy_key — one executable "
                     "cache per DecodeEngine instance at site "
                     "serving.decode; post-warmup compiles are ZERO by "
                     "construction (every bucket AOT-compiled in "
                     "warmup()), carry state donated per step so replay "
                     "never allocates; the page table rides as a TRACED "
                     "gather/scatter index argument, never a new shape",
    },
    ("mxtpu/serving/decode.py", "_build_draft_jit"): {
        "site": "serving.draft",
        "reason": "DecodeEngine._build_draft_jit is the compile front "
                  "door for the speculative-decoding DRAFT executables "
                  "(k-token proposal loop per cohort capacity bucket); "
                  "it resolves every miss through "
                  "compile_service.get_or_build at the engine's draft "
                  "site (default serving.draft — per-INSTANCE, so the "
                  "static rule sees '<dynamic>') and is AOT-warmed by "
                  "warmup() exactly like the target-family buckets; an "
                  "out-of-band draft jit anywhere else is a finding",
        "cache_key": "(kind draft, cohort capacity bucket, spec_k, draft "
                     "kv layout, vocab, draft param specs) + "
                     "registry.policy_key — the sixth entry in the "
                     "caches inventory; post-warmup compiles at "
                     "serving.draft are ZERO (watchdog-pinned by the "
                     "decode bench gate)",
    },
    ("mxtpu/optimizer_fused.py", "_build_guarded"): {
        "site": "fused_optimizer",
        "service": True,
        "reason": "same compile-service front door as _build; the guard "
                  "bit and scaler_cfg join the cache key in _cached_jit",
        "cache_key": "(optimizer class, static config, per-param specs "
                     "incl. sharding tokens, MeshPlan fingerprint, "
                     "guard bit, scaler_cfg) + registry.policy_key — "
                     "FusedUpdater._cached_jit; the mesh-native Trainer "
                     "shares this cache",
    },
}


@dataclass
class LintConfig:
    """Resolved analyzer configuration. ``root`` anchors every relative
    path in this object (CLI paths, policy_key_module, env_doc, scopes)."""

    root: Path
    policy_key_module: str = DEFAULT_POLICY_KEY_MODULE
    trace_scopes: Tuple[str, ...] = DEFAULT_TRACE_SCOPES
    env_doc: str = DEFAULT_ENV_DOC
    env_extra_roots: Tuple[str, ...] = DEFAULT_ENV_EXTRA_ROOTS
    metric_doc: str = DEFAULT_METRIC_DOC
    metric_scopes: Tuple[str, ...] = DEFAULT_METRIC_SCOPES
    exclude: Tuple[str, ...] = DEFAULT_EXCLUDE
    service_scopes: Tuple[str, ...] = DEFAULT_SERVICE_SCOPES
    jit_allowlist: Dict[Tuple[str, str], Dict[str, str]] = field(
        default_factory=lambda: dict(JIT_ALLOWLIST))

    def __post_init__(self):
        self.root = Path(self.root).resolve()

    def is_excluded(self, rel: str) -> bool:
        return any(rel == e or rel.startswith(e.rstrip("/") + "/")
                   for e in self.exclude)

    def in_trace_scope(self, rel: str) -> bool:
        for s in self.trace_scopes:
            if s in ("", "."):
                return True
            if rel == s or rel.startswith(s.rstrip("/") + "/"):
                return True
        return False
