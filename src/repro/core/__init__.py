"""Compartmentalized state machine replication - the paper's contribution.

Correctness plane (deterministic, message-level):
  protocols.CompartmentalizedMultiPaxos / vanilla_multipaxos /
  UnreplicatedStateMachine, mencius.MenciusDeployment,
  spaxos.SPaxosDeployment, craq.CraqDeployment
  + linearizability checkers.

Performance plane (JAX, calibrated on the paper's anchors):
  api.* the public surface: the pluggable variant registry
  (VariantSpec / register_variant - a protocol is a declared knob space,
  not a branch in a sweep loop) and the Workload dataclass (write mix,
  skew, arrival and batch-fill hints, passed once), analytical.* demand
  tables + bottleneck law for every registered variant,
  simulator.mva_curve / fluid_throughput / des_throughput, transient.*
  scripted dynamics, sweep.* batched mixed-variant surfaces, autotune.*
  budget search (autotune_variants across protocols).

The two planes meet in the registry: a variant that also declares an
ExecutableSpec (register_executable) executes its real cluster through
execution.run_variant - Workload-shaped traffic, linearizability check,
measured per-station msgs/cmd in canonical STATION_ORDER slots - and
execution.validate_variant reports measured-vs-analytical parity;
calibrate_alpha(measured=True) anchors alpha on an executed vanilla run.
batched_execution.* lowers those execution planes into the transient
plane's jitted scan - run_variant_batched / CompiledSweep.execute run a
whole (config x seed) grid of closed-loop clients in one device call and
emit measured msgs/cmd + latency histograms (validate_batched for parity).

Geo plane: api.GeoSpec (regions + RTT matrix + placement + client
weights) threads one WAN description through all three planes - geo.*
lowers each variant's message flow to per-region critical-path wire
latency (predict_geo_latency / wan_offsets), CompiledSweep.geo_latency
composes it with the jitted MVA queueing into a (config x region)
surface, autotune.autotune_placement searches placements under a budget,
execution.run_variant(geo=...) realizes the matrix on the real cluster
(per-region measured-vs-predicted parity via validate_variant), and
execute_configs(geo=...) fans the batched plane into per-region lanes;
transient.region_partition_schedule scripts a region dropping off the
WAN.

Autoscale plane: api.AutoscalePolicy (utilization band, hysteresis
guard, cooldown, floors/ceilings, machine budget) drives
autoscale.Controller / autoscale_grid - a closed loop on the transient
engine's own measured signals that resizes stations one server at a
time, each resize paying a transient.reconfiguration_schedule demand
spike; CompiledSweep.autoscale evaluates a whole (config x policy) grid
with one batched replay, autotune.autotune_policy ranks policies
against the frozen static baseline, and execution.run_autoscaled
replays the emitted plan on a real registered-variant cluster
(registry-derived live resize via resize_config / station_knob_map,
linearizable across every epoch, warm-phase dips parity-checked
against the transient prediction);
batched_execution.measured_capacity anchors the utilization law on the
execution plane.
"""
from .api import (
    MIXED_50_50,
    READ_HEAVY,
    UNSHARDED,
    WRITE_ONLY,
    AutoscalePolicy,
    ExecutableSpec,
    GeoSpec,
    Knob,
    ShardingSpec,
    VariantSpec,
    Workload,
    as_f_write,
    executable_variants,
    knob,
    register_executable,
    register_variant,
    registered_variants,
    resolve_workload,
    temporary_variants,
    unregister_variant,
    variant_spec,
)
from .analytical import (
    STATION_ORDER,
    VARIANT_MODELS,
    DeploymentModel,
    Station,
    ablation_steps,
    calibrate_alpha,
    compartmentalized_model,
    craq_chain_model,
    craq_model,
    craq_station_demands,
    effective_batch_size,
    grids_under,
    majority_grid,
    mencius_model,
    mixed_workload_speedup,
    multipaxos_model,
    read_scalability_law,
    spaxos_model,
    stack_demands,
    unreplicated_model,
    vanilla_mencius_model,
    vanilla_spaxos_model,
)
from .autoscale import (
    AutoscaleAction,
    AutoscaleTrace,
    Controller,
    autoscale_grid,
    diurnal_load,
    flash_crowd_load,
)
from .batched_execution import (
    BatchedExecutionResult,
    BatchedParityReport,
    batched_parity,
    execute_configs,
    measured_capacity,
    run_variant_batched,
    validate_batched,
)
from .autotune import (
    AutotuneResult,
    PlacementAutotuneResult,
    PlacementChoice,
    PolicyAutotuneResult,
    PolicyChoice,
    ShardChoice,
    ShardedAutotuneResult,
    TraceStep,
    VariantAutotuneResult,
    VariantChoice,
    autotune,
    autotune_placement,
    autotune_policy,
    autotune_sharded,
    autotune_variants,
    bottleneck_trace,
    variant_candidate_configs,
)
from .bpaxos import BPaxosDeployment, bpaxos_model
from .cluster import Network, Node
from .craq import CraqDeployment
from .execution import (
    AutoscaledExecutionTrace,
    ExecutionTrace,
    ParityReport,
    ShardedDeployment,
    ShardedExecutionTrace,
    ShardedParityReport,
    StationParity,
    default_config,
    resizable_stations,
    resize_config,
    run_autoscaled,
    run_sharded,
    run_variant,
    station_knob_map,
    validate_sharded,
    validate_variant,
    workload_ops,
)
from .geo import (
    GeoLatency,
    geo_station_kinds,
    geo_variants,
    placement_candidates,
    predict_geo_latency,
    register_geo_path,
    wan_offsets,
    zero_rtt,
)
from .history import History, Operation
from .iss import IssDeployment, iss_model
from .linearizability import (
    check_linearizable,
    check_register_reads,
    check_slot_order,
)
from .mencius import MenciusDeployment
from .messages import Command, noop_command
from .sharding import (
    check_linearizable_partitioned,
    flatten_shards,
    partition_history,
    partition_ops,
    shard_column,
    shard_demands,
    shard_weights,
    split_counts,
    split_weights,
)
from .protocols import (
    CompartmentalizedMultiPaxos,
    DeploymentConfig,
    UnreplicatedStateMachine,
    full_compartmentalized,
    vanilla_multipaxos,
)
from .quorums import GridQuorums, MajorityQuorums
from .simulator import (
    des_throughput,
    fluid_throughput,
    fluid_throughput_batch,
    mva_curve,
    mva_curves_batch,
    mva_curves_from_demands,
)
from .spaxos import SPaxosDeployment
from .sweep import (
    CompiledSweep,
    GeoLatencySurface,
    SweepSpec,
    compile_models,
    compile_sweep,
    config_variant,
    model_for,
)
from .transient import (
    CRASH,
    Event,
    TransientResult,
    build_schedule,
    burst_events,
    failover_schedule,
    mencius_skip_storm_schedule,
    reconfiguration_schedule,
    region_partition_schedule,
    resharding_schedule,
    scale_schedule,
    schedule_from_demands,
    simulate_transient,
    spaxos_payload_ramp_schedule,
    transient_throughput,
)
from .statemachine import AppendLog, KVStore, Register, make_state_machine

__all__ = [
    "MIXED_50_50", "READ_HEAVY", "UNSHARDED", "WRITE_ONLY",
    "AppendLog", "AutoscaleAction", "AutoscalePolicy", "AutoscaleTrace",
    "AutoscaledExecutionTrace", "AutotuneResult", "BPaxosDeployment",
    "BatchedExecutionResult",
    "BatchedParityReport", "CRASH", "Command",
    "CompartmentalizedMultiPaxos", "CompiledSweep", "Controller",
    "CraqDeployment",
    "DeploymentConfig", "DeploymentModel", "Event", "ExecutableSpec",
    "ExecutionTrace", "GeoLatency", "GeoLatencySurface", "GeoSpec",
    "GridQuorums", "History", "IssDeployment",
    "KVStore", "Knob", "MajorityQuorums", "MenciusDeployment", "Network",
    "Node", "Operation", "ParityReport", "PlacementAutotuneResult",
    "PlacementChoice", "PolicyAutotuneResult", "PolicyChoice", "Register",
    "SPaxosDeployment",
    "STATION_ORDER", "ShardChoice", "ShardedAutotuneResult",
    "ShardedDeployment", "ShardedExecutionTrace", "ShardedParityReport",
    "ShardingSpec", "Station", "StationParity", "SweepSpec", "TraceStep",
    "TransientResult",
    "UnreplicatedStateMachine", "VARIANT_MODELS", "VariantAutotuneResult",
    "VariantChoice", "VariantSpec", "Workload",
    "ablation_steps", "as_f_write", "autoscale_grid", "autotune",
    "autotune_placement",
    "autotune_policy", "autotune_sharded",
    "autotune_variants", "batched_parity",
    "bottleneck_trace", "bpaxos_model", "build_schedule", "burst_events",
    "calibrate_alpha",
    "check_linearizable", "check_linearizable_partitioned",
    "check_register_reads", "check_slot_order",
    "compartmentalized_model", "compile_models", "compile_sweep",
    "config_variant", "craq_chain_model", "craq_model",
    "craq_station_demands", "default_config", "des_throughput",
    "diurnal_load",
    "execute_configs",
    "effective_batch_size", "executable_variants",
    "failover_schedule", "flash_crowd_load", "flatten_shards",
    "fluid_throughput", "fluid_throughput_batch",
    "full_compartmentalized", "geo_station_kinds", "geo_variants",
    "grids_under", "iss_model", "knob", "majority_grid",
    "make_state_machine", "measured_capacity",
    "mencius_model", "mencius_skip_storm_schedule", "mixed_workload_speedup",
    "model_for", "multipaxos_model", "mva_curve", "mva_curves_batch",
    "mva_curves_from_demands", "noop_command",
    "partition_history", "partition_ops", "placement_candidates",
    "predict_geo_latency", "read_scalability_law",
    "reconfiguration_schedule",
    "register_executable", "register_geo_path", "register_variant",
    "registered_variants",
    "region_partition_schedule", "resharding_schedule", "resizable_stations",
    "resize_config", "resolve_workload",
    "run_autoscaled", "run_sharded", "run_variant", "run_variant_batched",
    "scale_schedule", "schedule_from_demands",
    "shard_column", "shard_demands", "shard_weights", "simulate_transient",
    "station_knob_map",
    "spaxos_model", "spaxos_payload_ramp_schedule",
    "split_counts", "split_weights", "stack_demands",
    "temporary_variants", "transient_throughput", "unregister_variant",
    "unreplicated_model",
    "validate_batched", "validate_sharded", "validate_variant",
    "vanilla_mencius_model", "vanilla_multipaxos",
    "vanilla_spaxos_model",
    "variant_candidate_configs", "variant_spec", "wan_offsets",
    "workload_ops", "zero_rtt",
]
