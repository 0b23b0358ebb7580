"""OCCL core: the deadlock-free collective execution framework (DFCE).

The paper's primary contribution, adapted TPU-natively: collectives are
per-rank primitive sequences over connector ring buffers, executed by a
long-running daemon loop with decentralized preemption (spin thresholds)
and stickiness-driven emergent gang-scheduling.  See DESIGN.md.
"""
from .algos import (AUTO_CANDIDATES, PLAN_BUILDERS, CompositePlan,
                    SubCollective, build_plan, default_hierarchy,
                    plan_hybrid, plan_torus, plan_tree_broadcast,
                    plan_tree_reduce, plan_two_level,
                    plan_two_level_alltoall, register_plan, select_algo)
from .config import OcclConfig, OrderPolicy, ReduceOp
from .costmodel import CostModel, fit, plan_features
from .daemon import (TickFlags, build_mesh_tick, build_shardmap_tick,
                     build_sim_tick, launch_prologue)
from .device_api import DeviceApi, decode_state, encode_state, encoded_zeros
from .errors import (ConnDepthWarning, DeadlockTimeout, EvictionError,
                     RegistrationClosed, StepTimeout)
from .handles import CollectiveHandle
from .recorder import (EVENT_NAMES, Diagnosis, FlightEvent, StalledChain,
                       diagnose, events)
from .primitives import CollKind, CollectiveSpec, Communicator, Prim
from .runtime import OcclRuntime, registered_heap_elems
from .staging import StagingEngine
from .deadlock import run_static_order, consistent_order_exists

__all__ = [
    "OcclConfig", "OrderPolicy", "ReduceOp",
    "CollKind", "CollectiveSpec", "Communicator", "Prim",
    "OcclRuntime", "registered_heap_elems", "DeadlockTimeout",
    "ConnDepthWarning", "StagingEngine",
    "EvictionError", "RegistrationClosed", "StepTimeout",
    "CollectiveHandle",
    "FlightEvent", "StalledChain", "Diagnosis", "EVENT_NAMES",
    "events", "diagnose",
    "TickFlags", "launch_prologue", "build_sim_tick", "build_mesh_tick",
    "build_shardmap_tick", "DeviceApi", "encode_state", "decode_state",
    "encoded_zeros",
    "run_static_order", "consistent_order_exists",
    "CompositePlan", "SubCollective", "default_hierarchy",
    "plan_two_level", "plan_torus", "plan_hybrid",
    "plan_tree_broadcast", "plan_tree_reduce", "plan_two_level_alltoall",
    "PLAN_BUILDERS", "AUTO_CANDIDATES", "register_plan", "build_plan",
    "select_algo", "CostModel", "plan_features", "fit",
]
