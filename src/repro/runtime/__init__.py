from repro.runtime.sharding import (  # noqa: F401
    cache_specs, constrain_client_batch, constrain_state, fit_spec,
    param_specs, shardings_for, state_client_axis, state_specs,
)
from repro.runtime.straggler import (  # noqa: F401
    PHASES, SpeedModel, deadline_survivors, pipelined_makespan,
    population_speed_draws, serial_step_times,
)
from repro.runtime.elastic import ClientPool  # noqa: F401
from repro.runtime.traces import (  # noqa: F401
    ConstantTrace, FileTrace, SyntheticTrace, Trace, load_trace,
    make_trace_gen,
)
from repro.runtime.population import (  # noqa: F401
    CohortSampler, PopulationStore,
)
