# Declarative experiment API: build an ExperimentSpec, call
# run_experiment, get a Trace.  See spec.py for the schema, registry.py
# for the solver table, runner.py for materialization + substrate
# dispatch.
from repro.api.spec import (
    ExperimentSpec, ProblemSpec, TopologySpec, InitSpec, SolverSpec,
    EngineSpec, CommSpec, SystemSpec, GRAPH_FAMILIES, WEIGHT_SCHEMES,
    SUBSTRATES, AVAILABILITY_KINDS,
)
from repro.api.registry import (
    SOLVERS, SolverDef, register_solver, get_solver, solver_names,
)
from repro.api.runner import (
    Trace, Materialized, run_experiment, materialize, simulate,
    comm_time_axis, system_time_axis,
)
