"""A controller in a module of its own (not benchmarks/agents.py): what a
shape's ``AGENT_MODULE`` is for. It answers as ``StatusEcho`` does."""

from benchmarks import agents


class ToyEcho(agents.StatusEcho):
    pass
