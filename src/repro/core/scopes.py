"""Names of the parts of the planner's and the executor's programs.

Each part runs under ``jax.named_scope(<name>)``, so every operation XLA
emits for it carries the name in its ``op_name`` metadata
(``jit(plan_query_batch)/vmap(planner.cardinality)/...``); a profiler
trace's operations can then be summed per part. Where scopes nest, the
innermost part wins.
"""

# planner (plan_query_batch): estimator and plangen
CARDINALITY = "planner.cardinality"  # star and per-relaxation join sizes
JOINABILITY = "planner.joinability"  # (T, R) joinable-key counts
ESTIMATE = "planner.estimate"        # pmfs, convolutions, order stats, plan

# executor (run_query_stream_with_masks): _execute_refill and _step
ADMIT = "executor.admit"    # stream gathers, initial and on refill, splice
PULL = "executor.pull"      # head scores, t*, one block pulled and deduped
PROBE = "executor.probe"    # seen-ring lookups: t*'s own and the join probe
TOPK = "executor.topk"      # top-k merge
APPEND = "executor.append"  # seen-ring append, counters and cursors
BOUND = "executor.bound"    # HRJN corner bound and termination

PLANNER = (CARDINALITY, JOINABILITY, ESTIMATE)
EXECUTOR = (ADMIT, PULL, PROBE, TOPK, APPEND, BOUND)
