from repro.serve.engine import (RECOMPILE, RESIDENT, Completion,
                                EngineSession, FleetConfig,
                                FleetServeEngine, FleetSession, Request,
                                ServeConfig, ServeEngine, ServeSession,
                                percentile, reference_decode,
                                reference_margins, validate_requests)
from repro.serve.frontend import (BLOCK, EDF, FIFO, REJECT, SHED_LATEST,
                                  Frontend, FrontendConfig, summarize)
from repro.serve.traffic import (ClosedLoop, Diurnal, FlashCrowd,
                                 LengthModel, Poisson, Workload,
                                 bounded_pareto, synthetic_workload,
                                 with_deadlines)

__all__ = ["ServeConfig", "ServeEngine", "Request", "Completion",
           "RECOMPILE", "RESIDENT", "reference_decode", "reference_margins",
           "synthetic_workload", "percentile", "FleetConfig",
           "FleetServeEngine", "validate_requests",
           # streaming session API
           "ServeSession", "EngineSession", "FleetSession",
           # traffic generators
           "Workload", "ClosedLoop", "Poisson", "Diurnal", "FlashCrowd",
           "LengthModel", "bounded_pareto", "with_deadlines",
           # admission front end
           "Frontend", "FrontendConfig", "summarize",
           "BLOCK", "REJECT", "SHED_LATEST", "EDF", "FIFO"]
