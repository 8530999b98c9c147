"""Core SSR library: the paper's contribution as composable JAX modules.

Paper-section map (details per module, full table in DESIGN.md §2):
``stream`` (§2/§3.1 AGU config registers), ``agu`` (§3.1 address
generation), ``ssr`` (§2 stream-semantic operand delivery), ``compiler``
(§3.2 SSR-ification pass + chaining), ``lowering`` (§3.2 step 4–5: config
emission and region execution), ``autotune`` (schedule search: cost-model
prune + measured winners in a persistent cache), ``isa`` (§4/§5 exact cost
models), ``region`` (§2.2.2 ``ssrcfg`` CSR).
"""

from .stream import (  # noqa: F401
    Direction,
    MAX_DIMS,
    StreamSpec,
    contiguous,
    strided_2d,
    validate_no_race,
)
from .agu import (  # noqa: F401
    address_sequence,
    affine_coefficients,
    block_grid,
    gather_stream,
    scatter_stream,
)
from .isa import (  # noqa: F401
    HotLoop,
    KernelModel,
    Table2Row,
    breakeven_lhs,
    breakeven_rhs,
    cluster_time,
    equivalent_cores,
    fig4_dot_product,
    kernel_suite,
    min_side_length,
    n_base,
    n_ssr,
    ssr_profitable,
    table2,
    utilization_class,
    utilization_limit_dot,
    utilization_reduction,
)
from .ssr import (  # noqa: F401
    BlockStream,
    StreamReport,
    VMEM_BUDGET_BYTES,
    auto_block,
    check_mxu_alignment,
    ssr_pallas,
)
from . import nest_analysis  # noqa: F401
from .compiler import (  # noqa: F401
    Allocation,
    Band,
    COMBINE_COST,
    ChainDAG,
    ChainError,
    ChainLink,
    ChainedPlan,
    ClusterReport,
    CoreCost,
    DagEdge,
    LoopNest,
    MemRef,
    StreamPlan,
    attention_nest,
    chain,
    chain_dag,
    cluster_cost,
    dot_product_nest,
    elementwise_nest,
    gemm_nest,
    gemv_nest,
    iso_performance_cores,
    spmm_nest,
    spmv_nest,
    ssrify,
    stencil2d_nest,
    stencil_nest,
)
from .lowering import (  # noqa: F401
    BlockPolicy,
    DEFAULT_POLICY,
    DEFAULT_SCHEDULE,
    LoweredChain,
    LoweredNest,
    LoweredPlan,
    LoweredStream,
    LoweringError,
    NestStream,
    Schedule,
    lower_chain,
    lower_nest,
    lower_plan,
    plan_stats,
    ssr_call,
    ssr_chain_call,
    ssr_dag_call,
)
from . import autotune  # noqa: F401
from .autotune import (  # noqa: F401
    ScheduleCache,
    TuneResult,
    candidate_schedules,
    schedule_is_legal,
)
from .region import ssr_enabled, ssr_region, set_ssr  # noqa: F401
