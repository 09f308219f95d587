"""The paper's contribution: doubly stochastic empirical kernel learning."""
from repro.core.dsekl import (  # noqa: F401
    DSEKLConfig, DSEKLState, init_state, step_serial, epoch_parallel,
    grad_block, apply_update,
    decision_function, decision_function_ref, decision_function_source,
    predict_labels, streaming_train_pass, support_vectors, truncate,
)
from repro.core.precond import (  # noqa: F401
    EigenProPreconditioner, estimate_preconditioner,
)
from repro.core.solver import (  # noqa: F401
    fit, FitResult, error_rate, train_epoch_hosted,
)
from repro.core.trainer import (  # noqa: F401
    ExecutionPlan, SerialPlan, ParallelPlan, HostedPlan, MeshPlan,
    fit_loop, make_plan, resolve_execution,
)
