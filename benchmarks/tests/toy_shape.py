"""The ConfigMap shape with its controller named in another module: a
configuration reaches it by the dotted name ``benchmarks.tests.toy_shape``."""

from benchmarks.shapes.configmap import *  # noqa: F401,F403

AGENT = "ToyEcho"
AGENT_MODULE = "benchmarks.tests.toy_agents"
