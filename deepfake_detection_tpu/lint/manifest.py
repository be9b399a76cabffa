"""The project manifest dfdlint runs against — the single declarative
statement of which modules/attributes carry which invariants.

Every entry here is a *promise the rest of the repo makes*:

* ``JAX_FREE_MODULES`` — modules whose import must never reach jax/flax
  transitively (PR 1's spawned-worker import discipline; spawned shm
  decode workers, data-prep hosts and reporting subprocesses import
  these with no accelerator stack).  DFD001 proves it on the static
  import graph; one subprocess canary in tests/test_lint.py proves the
  graph against reality.
* ``DONATING_FACTORIES`` — factory functions whose *returned* callable
  donates argument buffers (``donate_argnums``): reading a value after
  passing it to one is the PR 2/PR 3 use-after-free class.
* ``RNG_DIRS`` — subtrees where every random draw must derive from the
  absolute ``(seed, epoch, index)`` streams or an injected Generator
  (bit-identical resume depends on it).
* ``METRIC_REGISTRIES`` — the modules allowed to register ``dfd_*``
  Prometheus names, one prefix each; every literal reference elsewhere
  must resolve to a registered name (a typo'd metric is a silently dead
  dashboard).  ``METRIC_DYNAMIC_PREFIXES`` marks families registered
  from runtime dicts (obs collectors) that static analysis cannot
  enumerate.
* ``LOCK_GUARDED`` — (file, attribute, lock) triples where a mutation
  outside ``with <lock>`` re-opens the PR 10 split-lock gauge bug.
* ``CHAOS_MODULE`` — where the ``KNOWN_POINTS`` injection-point registry
  lives; a ``fires("typo", ...)`` probe or a ``name@step`` spec literal
  naming an unknown point is a dead injection path.
* ``CTYPES_EXEMPT`` — the one module allowed to bind ``dfd_*`` native
  symbols without its own ABI-version probe (it owns the probe).
* ``SHARD_MAP_ALLOWLIST`` — legacy manual-SPMD modules still allowed to
  call ``shard_map``/``pmap`` directly; everything else must express
  parallelism as NamedSharding under plain jit (DFD010, ISSUE 12).
"""

from __future__ import annotations

from .core import LintConfig

# Modules that must stay importable with zero jax in sys.modules.
# Note the graph includes ancestor packages: declaring a submodule
# jax-free also pins every ``__init__.py`` above it.
JAX_FREE_MODULES = (
    "deepfake_detection_tpu",               # top-level __init__ (registry+config)
    "deepfake_detection_tpu.chaos",
    "deepfake_detection_tpu.data",          # lazy __init__ (PEP 562)
    "deepfake_detection_tpu.data.packed",
    "deepfake_detection_tpu.data.native",
    "deepfake_detection_tpu.data.shm_ring",
    "deepfake_detection_tpu.obs",           # lazy __init__ (PEP 562)
    "deepfake_detection_tpu.obs.events",
    "deepfake_detection_tpu.streaming.ring",
    "deepfake_detection_tpu.streaming.tracker",
    "deepfake_detection_tpu.streaming.verdict",
    "deepfake_detection_tpu.lint",          # the linter itself
    # backfill worker-side modules: the chaos harness, make_lists
    # manifest emission and book audits run with no accelerator stack
    # (only runners/backfill.py touches jax)
    "deepfake_detection_tpu.backfill",
    "deepfake_detection_tpu.backfill.manifest",
    "deepfake_detection_tpu.backfill.lease",
    "deepfake_detection_tpu.backfill.writer",
    "deepfake_detection_tpu.backfill.source",
    # the fleet router tier (ISSUE 15): the routing process must never
    # pay — or wait on — an accelerator import; replicas are separate
    # processes that do.  utils.prometheus is the jax-free observability
    # floor these share (utils/__init__ is PEP-562 lazy for exactly this)
    # the verdict-cache core (ISSUE 17): numpy+hashlib only, shared by
    # the router edge probe and the backfill dedup pass — both run in
    # processes that never import jax
    "deepfake_detection_tpu.cache",
    "deepfake_detection_tpu.cache.content",
    "deepfake_detection_tpu.cache.store",
    # warm-start key/manifest schema (ISSUE 19): the store KEY must be
    # computable by jax-free tooling (bench reporters, fleet ops); only
    # serving.warmstart (serialize/deserialize) touches jax
    "deepfake_detection_tpu.serving.warmkey",
    "deepfake_detection_tpu.fleet",
    "deepfake_detection_tpu.fleet.registry",
    "deepfake_detection_tpu.fleet.metrics",
    "deepfake_detection_tpu.fleet.controller",
    "deepfake_detection_tpu.fleet.migrate",
    "deepfake_detection_tpu.fleet.router",
    "deepfake_detection_tpu.fleet.dataplane",
    # the ISSUE 18 control loop: SLO autoscaler + backfill tenant glue
    # run in the router process (decisions must never wait on jax)
    "deepfake_detection_tpu.fleet.autoscaler",
    "deepfake_detection_tpu.runners.router",
    "tools.pack_dataset",
    "tools.obs_report",
    "tools.make_lists",
    "tools.dfdlint",
)

DONATING_FACTORIES = {
    # train/steps.py: returned step donates the TrainState (argument 0)
    "make_train_step": (0,),
}

RNG_DIRS = (
    "deepfake_detection_tpu/data",
    "deepfake_detection_tpu/streaming",
    "deepfake_detection_tpu/serving",
    "deepfake_detection_tpu/fleet",
)

METRIC_REGISTRIES = {
    "deepfake_detection_tpu/serving/metrics.py": "dfd_serving",
    "deepfake_detection_tpu/streaming/metrics.py": "dfd_streaming",
    "deepfake_detection_tpu/obs/telemetry.py": "dfd_train",
    "deepfake_detection_tpu/fleet/metrics.py": "dfd_router",
}

# obs collectors register gauge/counter names from runtime dicts (loader
# stats, resilience counters) — those families cannot be enumerated
# statically, so literal references under these prefixes are not checked
METRIC_DYNAMIC_PREFIXES = (
    "dfd_train_",
)

LOCK_GUARDED = (
    # the PR 10 incident: inflight gauge bump/decrement must be one atom
    # with the _pending ledger mutation, under the ledger's own lock
    ("deepfake_detection_tpu/serving/engine.py", "inflight",
     "_pending_lock"),
)

CHAOS_MODULE = "deepfake_detection_tpu/chaos.py"

CTYPES_EXEMPT = (
    "deepfake_detection_tpu/data/native.py",    # owns the ABI probe
)

# Modules still allowed to call shard_map/pmap directly ("legacy manual
# SPMD").  The ISSUE 12 migration unified training on NamedSharding under
# plain jit; these two genuinely need manual per-device programs —
# collective-permute rings (ring attention) and pipeline ppermute hops —
# and each rides here only until its own migration.  DFD010 rot-checks
# the list: an entry whose file stops calling shard_map fails the gate.
SHARD_MAP_ALLOWLIST = (
    "deepfake_detection_tpu/parallel/ring_attention.py",
    "deepfake_detection_tpu/parallel/pp.py",
)


def default_config() -> LintConfig:
    return LintConfig(
        jax_free_modules=JAX_FREE_MODULES,
        donating_factories=dict(DONATING_FACTORIES),
        rng_dirs=RNG_DIRS,
        metric_registries=dict(METRIC_REGISTRIES),
        metric_dynamic_prefixes=METRIC_DYNAMIC_PREFIXES,
        lock_guarded=LOCK_GUARDED,
        chaos_module=CHAOS_MODULE,
        ctypes_exempt=CTYPES_EXEMPT,
        shard_map_allowlist=SHARD_MAP_ALLOWLIST,
    )
