from repro.serving.engine import ServingEngine
from repro.serving.batcher import Request
from repro.serving.async_engine import AsyncRouterEngine
from repro.serving.policy_router import DevicePolicyRouter
from repro.serving.faults import DecideFault, ScriptedFaults
from repro.serving.storm import run_storm
from repro.serving.traffic import (
    TRAFFIC_PATTERNS,
    outages_from_scenario,
    wave_sizes,
)

__all__ = [
    "ServingEngine", "Request", "AsyncRouterEngine", "DevicePolicyRouter",
    "DecideFault", "ScriptedFaults", "run_storm", "TRAFFIC_PATTERNS",
    "outages_from_scenario", "wave_sizes",
]
