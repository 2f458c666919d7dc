"""The engine's host spans reach a profiler trace under their exact
names, as ``bench.trace.load`` reads it: the step annotation's number
stays out of ``engine.iteration``'s name, and the annotations are opened
under ``NULL_REGISTRY`` too."""
import glob
import os

import jax

from bench import trace
from repro.core.hypertrick import RandomSearchPolicy
from repro.core.search_space import Categorical, LogUniform, SearchSpace
from repro.core.service import OptimizationService
from repro.population.engine import LocalDriver, PopulationEngine
from repro.telemetry import NULL_REGISTRY


def test_engine_spans_land_in_the_profiler_trace(tmp_path):
    space = SearchSpace({"learning_rate": LogUniform(1e-4, 1e-3),
                         "t_max": Categorical((2,)),
                         "gamma": Categorical((0.99,))})
    svc = OptimizationService(RandomSearchPolicy(space, 2, 1, seed=0))
    engine = PopulationEngine("pong", max_slots=2, n_envs=2,
                              episodes_per_phase=2, max_updates=3, seed=0,
                              metrics=NULL_REGISTRY)
    jax.profiler.start_trace(str(tmp_path))
    try:
        engine.run(LocalDriver(svc))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    names = {name for plane in trace.load(path)
             if not trace.is_device(plane["name"])
             for events in plane["lines"].values() for name, _, _ in events}
    assert {"engine.iteration", "engine.dispatch", "engine.sync",
            "engine.admit", "engine.report"} <= names
