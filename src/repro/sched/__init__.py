"""repro.sched: streaming scheduler engine, scenario suite, and service
drivers layered over repro.core (see docs/ARCHITECTURE.md)."""
from repro.sched.engine import (DEFAULT_QUEUE_WINDOW, EngineHooks,
                                EngineSnapshot, MultiHooks,
                                PolicyPrioritizer, Prioritizer,
                                SchedulerEngine)
from repro.sched.scenarios import (SCENARIOS, Scenario, ScenarioRun,
                                   get_scenario, list_scenarios, register)
from repro.sched.service import (QuotaPrioritizer, SlaLanePrioritizer,
                                 StreamResult, run_scenario, run_stream,
                                 wrap_tenancy)
from repro.sched.reference import ReferenceEngine, reference_run
from repro.sched.telemetry import (RollingTelemetry, TelemetrySample,
                                   jain_index)

__all__ = [
    "DEFAULT_QUEUE_WINDOW", "EngineHooks", "EngineSnapshot", "MultiHooks",
    "PolicyPrioritizer", "Prioritizer", "SchedulerEngine", "SCENARIOS",
    "Scenario", "ScenarioRun", "get_scenario", "list_scenarios", "register",
    "QuotaPrioritizer", "SlaLanePrioritizer", "StreamResult", "run_scenario",
    "run_stream", "wrap_tenancy", "ReferenceEngine", "reference_run",
    "RollingTelemetry", "TelemetrySample",
    "jain_index",
]
