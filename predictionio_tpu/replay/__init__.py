"""Scripted traffic replay: the production-day harness.

``workload`` is the one traffic generator (seeded open-loop schedules,
the closed-loop keep-alive measure loop, and the asyncio concurrent
client); ``scenario`` is the declarative scripted-day format;
``day`` drives the real fleet topology through a scenario and hands the
evidence to :mod:`predictionio_tpu.obs.verdict`.
"""

from predictionio_tpu.replay.scenario import Scenario, ScenarioError  # noqa: F401
from predictionio_tpu.replay.workload import (  # noqa: F401
    OpenLoopRunner,
    PhaseSchedule,
    build_phase_schedule,
    measure_closed_loop,
    schedule_digest,
)
