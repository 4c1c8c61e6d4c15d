"""DHP core — the paper's contribution: dynamic hybrid parallelism.

Public API:
  CostModel / CostCoeffs / SeqInfo / Hardware   (Eqs. 7-10)
  pack_sequences / AtomicGroup                  (Stage 1, BFD)
  allocate / allocate_bruteforce                (Stage 2, 2D-DP, Alg. 1)
  DHPScheduler / static_plan / ExecutionPlan    (Fig. 3 workflow)
  Profiler                                      (coefficient fitting)
  ClusterSimulator / end_to_end_table           (paper-table reproduction)
"""
from .allocator import (Allocation, IncrementalAllocator, allocate,
                        allocate_bruteforce, allocate_many,
                        allocate_reference, evaluate_degrees)
from .cost_model import (CostCoeffs, CostModel, Hardware, MMSequence,
                         ModalitySpan, SeqInfo, analytic_coeffs,
                         model_costs,
                         as_seq_infos, slice_spans, spans_eta,
                         synthesize_spans)
from .dataset_profiles import PROFILES, DatasetProfile, get_profile
from .distributions import DATASETS, sample_batch, sample_mm_batch
from .group_pool import (BUCKET_LADDERS, GroupPool, make_bucket_fn,
                         pow2_bucket)
from .packing import (AtomicGroup, flatten_group, pack_sequences,
                      packing_efficiency, validate_packing)
from .profiler import Profiler, profiling_grid
from .scheduler import (PLAN_IR_VERSION, DHPScheduler, ExecutionPlan,
                        GroupDelta, GroupPlan, MicroBatchPlan,
                        MicroBatchPlanner, PlanCache,
                        PlanValidationError, diff_plans, load_plans,
                        plans_from_json, plans_to_json, save_plans,
                        static_plan)
from .simulator import ClusterSimulator, end_to_end_table, scaling_table

__all__ = [
    "Allocation", "IncrementalAllocator", "allocate",
    "allocate_bruteforce", "allocate_many", "allocate_reference",
    "evaluate_degrees",
    "CostCoeffs", "CostModel", "Hardware", "SeqInfo", "analytic_coeffs",
    "model_costs",
    "MMSequence", "ModalitySpan", "as_seq_infos", "slice_spans",
    "spans_eta", "synthesize_spans",
    "DatasetProfile", "PROFILES", "get_profile",
    "DATASETS", "sample_batch", "sample_mm_batch",
    "AtomicGroup", "pack_sequences", "validate_packing",
    "flatten_group", "packing_efficiency",
    "BUCKET_LADDERS", "GroupPool", "make_bucket_fn", "pow2_bucket",
    "Profiler", "profiling_grid",
    "DHPScheduler", "ExecutionPlan", "GroupPlan", "MicroBatchPlan",
    "MicroBatchPlanner", "static_plan",
    "PLAN_IR_VERSION", "GroupDelta", "PlanCache",
    "PlanValidationError", "diff_plans",
    "plans_to_json", "plans_from_json", "save_plans", "load_plans",
    "ClusterSimulator", "end_to_end_table", "scaling_table",
]
