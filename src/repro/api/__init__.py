"""repro.api — the single public surface of the Seneca reproduction.

Live service (sessions over one shared cache + sampler)::

    from repro.api import SenecaServer

    server = SenecaServer.for_dataset(ds, cache_frac=0.35)
    with server.open_session(batch_size=32) as sess:
        ids, forms = sess.next_batch_ids()

Pluggable behavior: ``SenecaServer(cfg, backend="jax")`` swaps the ODS
metadata engine; ``sampler=`` / ``admission=`` / ``eviction=`` select
policies by registered name ("ods"/"naive", "unseen-only"/"capacity",
"refcount"/"lru"); :func:`register_policy` adds new ones.

Live repartitioning: ``SenecaConfig(repartition="adaptive")`` turns on
the telemetry-calibrated :class:`RepartitionController` — pipelines
report stage timings into :class:`TelemetryAggregator`, the controller
re-solves the MDP on the calibrated profile and resizes the cache split
in place when the predicted gain clears hysteresis (docs/API.md
"Telemetry + adaptive repartitioning").

The fluid-flow simulator behind the paper-figure benchmarks is re-exported
here too, so benchmark and example code imports one namespace only.  See
docs/API.md for the full tour.
"""
from repro.api.backends import (JaxOdsBackend, NumpyOdsBackend, OdsBackend,
                                backend_names, register_backend,
                                resolve_backend)
from repro.api.policies import (AdmissionPolicy, CapacityAdmission,
                                EvictionPolicy, LruEviction, NaiveSampler,
                                OdsSampler, RefcountEviction, SamplerPolicy,
                                UnseenOnlyAdmission, policy_names,
                                register_policy, resolve_policy)
from repro.api.server import (CODE_FORM, FORM_CODE, SLO,
                              RepartitionController, SenecaConfig,
                              SenecaServer, SenecaService, Session,
                              SessionClosed)
from repro.api.telemetry import (Ewma, TelemetryAggregator,
                                 TelemetrySnapshot)
# hardware / dataset profiles + the closed-form DSI model (Eqs. 1-9,
# plus the form×tier two-level variant behind the SSD spill engine)
from repro.core.perf_model import (AWS_P3, AZURE_NC96, DATASETS,
                                   EVAL_PROFILES, GB, Gbit, IMAGENET_1K,
                                   IMAGENET_22K, IN_HOUSE, KB, MB,
                                   OPENIMAGES, VALIDATION_PROFILES,
                                   DatasetProfile, HardwareProfile,
                                   JobProfile, dsi_throughput,
                                   dsi_throughput_tiered)
# mechanistic simulator (Table 7 loader matrix) for the fig* benchmarks
from repro.sim.desim import (ALL_LOADERS, DALI_CPU, DALI_GPU, DSISimulator,
                             LoaderSpec, MDP_ONLY, MINIO, PYTORCH, QUIVER,
                             SENECA, SHADE, SimJob, SimResult)
# sharded data plane (docs/API.md "Sharded data plane"): consistent-hash
# router + per-shard caches behind sim/process transports, selected via
# SenecaConfig(shards=N, shard_transport=...)
from repro.service import (CacheShard, ShardConfig, ShardedCache,
                           ShardRouter)
# fault injection + failover (docs/API.md "Fault tolerance & elasticity")
from repro.faults import (FAULT_KINDS, FaultInjector, FaultSpec,
                          LivenessRegistry)

# live multi-job workload runner + pluggable clocks (docs/API.md
# "Multi-job workloads"); VirtualClock makes concurrency deterministic.
# These are re-exported lazily (PEP 562): repro.workload.runner imports
# the pipeline, which imports repro.api.server, which initializes this
# package — an eager import here would close that cycle on a partially
# initialized module.
_WORKLOAD_EXPORTS = ("Clock", "JobResult", "JobSpec", "RealClock",
                     "VirtualClock", "WorkloadResult", "WorkloadRunner",
                     "deterministic_runner",
                     # open-loop serving (docs/API.md "Open-loop serving
                     # & SLOs")
                     "OpenLoopGenerator", "RequestResult", "ServeResult",
                     "ARRIVAL_PROCESSES", "poisson_arrivals",
                     "bursty_arrivals", "diurnal_arrivals",
                     "make_arrivals")


def __getattr__(name: str):
    if name in _WORKLOAD_EXPORTS:
        import repro.workload as _workload
        return getattr(_workload, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

__all__ = [
    # server / session facade
    "SenecaServer", "Session", "SessionClosed", "SenecaConfig",
    "SenecaService", "SLO", "FORM_CODE", "CODE_FORM",
    # telemetry + adaptive repartitioning
    "RepartitionController", "TelemetryAggregator", "TelemetrySnapshot",
    "Ewma",
    # policies
    "SamplerPolicy", "AdmissionPolicy", "EvictionPolicy",
    "OdsSampler", "NaiveSampler", "UnseenOnlyAdmission",
    "CapacityAdmission", "RefcountEviction", "LruEviction",
    "register_policy", "resolve_policy", "policy_names",
    # backends
    "OdsBackend", "NumpyOdsBackend", "JaxOdsBackend",
    "register_backend", "resolve_backend", "backend_names",
    # profiles + closed-form model
    "HardwareProfile", "DatasetProfile", "JobProfile", "dsi_throughput",
    "dsi_throughput_tiered",
    "AZURE_NC96", "AWS_P3", "IN_HOUSE", "VALIDATION_PROFILES",
    "EVAL_PROFILES", "DATASETS", "IMAGENET_1K", "IMAGENET_22K",
    "OPENIMAGES", "GB", "MB", "KB", "Gbit",
    # simulator
    "DSISimulator", "LoaderSpec", "SimJob", "SimResult", "ALL_LOADERS",
    "PYTORCH", "DALI_CPU", "DALI_GPU", "MINIO", "QUIVER", "SHADE",
    "MDP_ONLY", "SENECA",
    # live multi-job workloads
    "WorkloadRunner", "JobSpec", "JobResult", "WorkloadResult",
    "Clock", "RealClock", "VirtualClock", "deterministic_runner",
    # open-loop serving
    "OpenLoopGenerator", "RequestResult", "ServeResult",
    "ARRIVAL_PROCESSES", "poisson_arrivals", "bursty_arrivals",
    "diurnal_arrivals", "make_arrivals",
    # sharded data plane
    "ShardRouter", "ShardedCache", "CacheShard", "ShardConfig",
    # fault injection + failover
    "FaultSpec", "FaultInjector", "LivenessRegistry", "FAULT_KINDS",
]
