"""Deterministic scenario engine: dynamic scenes (object churn + tombstone
deletes), user/network/fleet traces, one discrete-event session loop.

The paper's headline device claims — sub-100 ms queries under network
drops, bounded memory at tens of thousands of objects, downstream bandwidth
∝ map changes (Sec. 3.2, Fig. 6) — only mean anything when the scene
*changes*.  This package makes the dynamic regime a first-class, replayable
workload: a seeded declarative ``Scenario`` (object lifecycle events, user
trajectories, network traces, fleet churn, knob schedule) driven by one
``ScenarioEngine`` loop that subsumes the ad-hoc session drivers
(examples/network_drop_session.py, sim.fleet.FleetSimulator are thin
wrappers) and emits a structured, bit-replayable ``MetricsLog``.
"""
from repro.core.runtime import FaultModel
from repro.sim.scenario import (ClientSpec, CrashEvent, GridSpec, KnobEvent,
                                NetTrace, ObjectEvent, PoseTrack, QueryPlan,
                                Scenario, churn_scenario)
from repro.sim.world import WorldState
from repro.sim.engine import MetricsLog, ScenarioEngine, run_scenario
from repro.sim.fleet import FleetSimulator, SimClient
