"""Streaming graph-clustering service on top of SPED.

The one-shot pipeline (edges → dilated reversed Laplacian → top-k solver
→ k-means) assumes a frozen graph; real graphs arrive as streams of edge
updates.  This subsystem turns the pipeline into a long-running,
multi-tenant service where re-clustering after an update costs a small
fraction of a cold solve: dilation keeps per-iteration contraction high,
warm starts keep iteration counts low, and first-order eigen-updates
skip the solver entirely for small perturbations.

Module map
----------
graph_store
    jit-stable mutable edge store: padded capacity classes (powers of
    two), fixed-size batched insert/delete/reweight upserts, lazy degree
    recomputation, EdgeList views consumable by every core operator.
warm
    Warm-started solver sessions: seed from the previous panel via
    solvers.init_from_panel, restart-vs-continue decided by the block
    residual of the old panel under the new operator, chunked
    run-to-tolerance loop (the reconvergence engine).
updates
    Dhanjal-style first-order incremental eigen-updates from realized
    edge-weight deltas, with an accumulated-drift bound that triggers
    automatic fallback to a full (warm-started) SPED re-solve.
service
    Multi-tenant session manager: admission into capacity classes with
    probe-driven DilationPlans (per-session lr/scale traced, per-class
    degree re-planned on the snapped planner grid), batched jitted
    ticks built by repro.core.program (one compiled program per
    (class, degree, layout, occupancy); the scheduler's per-session
    step multipliers ride as a traced input), the residual-decay
    tick scheduler, per-session convergence via panel residuals
    (converged sessions cost zero device work), eviction with panel
    caching (``add_graph(resume_panel=)`` re-admission), streaming
    updates routed through the incremental path, and label serving.
source
    The service's one operator source, chosen from (backend, mesh,
    model_axes): segment, node-blocked, edge-sharded segment or
    blocked, or panel-sharded.  It owns the shard-balanced edge
    capacities, the session's one cached layout, the group-key
    statics, the stacked tick operands and the admission probe; the
    tick programs themselves live in ``repro.core.program``.
tracking
    Stable cluster ids across re-solves: greedy maximum-overlap matching
    of each new k-means labelling onto the previous one.

Entry points: ``StreamingService`` for the service,
``benchmarks/bench_stream.py`` for updates/sec and
iterations-to-reconverge numbers, ``examples/streaming_clustering.py``
for an end-to-end walkthrough.
"""
from repro.stream.graph_store import (  # noqa: F401
    CAPACITY_CLASSES,
    BatchStats,
    EdgeBatch,
    GraphStore,
    apply_edge_batch,
    as_edge_list,
    capacity_class,
    coalesce_batch,
    from_edge_list,
    grow,
    make_edge_batch,
    num_edges,
    refresh_degrees,
)
from repro.stream.service import (  # noqa: F401
    ServiceConfig,
    StreamingService,
    UnknownSessionError,
    node_capacity_class,
)
from repro.stream.tracking import (  # noqa: F401
    LabelTracker,
    label_churn,
    match_labels,
)
from repro.stream.updates import (  # noqa: F401
    EigenEstimate,
    UpdateConfig,
    anchor_estimate_arrays,
    estimate_from_panel,
    first_order_update,
    should_fallback,
)
from repro.stream.warm import (  # noqa: F401
    WarmConfig,
    reconverge,
    run_to_tolerance,
    warm_start_state,
)
