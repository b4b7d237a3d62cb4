"""Public API of the AnotherMe semantic-trajectory engine.

    from repro.api import AnotherMeEngine, EngineConfig, ExecutionPlan

    engine = AnotherMeEngine(forest, EngineConfig(backend="ssh"))
    result = engine.run(batch)        # .similar_pairs / .communities / .stats

Components (all replaceable independently):

  AnotherMeEngine / EngineConfig / ExecutionPlan   one entry point,
      single-device jit or shard_map selected by ExecutionPlan(n_shards=...)
  StreamingEngine                                  micro-batch ingestion:
      engine.update(batch) appends into a device-resident world and scores
      only the delta pairs, with incremental community maintenance
  get_backend / register_backend / available_backends
      string-keyed candidate-backend registry ("ssh", "minhash", "brp", "udf")
  CandidateBackend / BackendContext                backend protocol
  EncodeStage / CandidateStage / ScoreStage / CommunitiesStage
      the typed stage pipeline the engine composes
  QueryEngine                                      online top-k serving:
      QueryEngine(stream).query(batch) probes the resident index read-only
      and returns per-query top-k (match id, mss) without mutating the world
  CapacityPlanner                                  buffer sizing + overflow retry
  CapacityExceeded                                 typed admission refusal: an
      update/query over the max_resident_bytes budget (or past the retry
      doublings) is refused with the world state untouched
  Instrumentation                                  phase times, profiler spans,
      compile counts per phase and stats of one run
  MeshStages                                       the one-shot job as
      staged shard_map programs, each planned from counts made in the mesh
  plan_capacities / DistributedPlan                 a plan's capacities;
      the host-numpy planner is the oracle of the in-mesh counts

The legacy ``repro.core.run_anotherme`` / ``AnotherMeConfig`` remain as a
deprecation shim over this API.
"""
from repro.api.backends import (
    BackendContext, BRPBackend, CallableBackend, CandidateBackend,
    MinHashBackend, SSHBackend, UDFBackend, available_backends, get_backend,
    register_backend,
)
from repro.api.capacity import CapacityPlanner
from repro.api.engine import (
    AnotherMeEngine, EngineConfig, EngineResult, ExecutionPlan,
)
from repro.api.errors import CapacityExceeded
from repro.api.instrumentation import Instrumentation
from repro.api.sharded import (
    DistributedPlan, MeshStages, StreamJoinPlan, StreamShardPlan,
    gather_similar_pairs,
    make_streaming_join_pipeline, make_streaming_score_pipeline,
    pad_to_shards, plan_capacities, plan_stream_capacities,
    plan_stream_join, sticky_join_plan,
)
from repro.api.serving import (
    QueryEngine, QueryPlan, QueryResult, make_query_probe_pipeline,
    make_query_score_pipeline, plan_query_capacities, sticky_query_plan,
)
from repro.api.stages import (
    LCS_IMPLS, CandidateStage, CommunitiesStage, EncodeStage, PipelineContext,
    ScoreStage, Stage, lcs_impl_fn, validate_lcs_impl,
)
from repro.api.streaming import StreamingEngine
