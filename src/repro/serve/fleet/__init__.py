from repro.serve.fleet.engine import FleetEngine
from repro.serve.fleet.scenarios import (SCENARIOS, ServedScenario,
                                         SineStream, adaptive_scenario,
                                         kws_scenario)
from repro.serve.fleet.sessions import Session, SessionTable
from repro.serve.fleet.traffic import PoissonTraffic, SessionSpec
