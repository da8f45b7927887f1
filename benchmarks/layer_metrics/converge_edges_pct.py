"""Share of a convergence outside the eight inner phases, measured and
not a remainder: over the operations joined at both ends
(``benchmarks/edge_join.py``), the means of ``sent - due``, ``rx -
sent``, ``t0 - rx`` and ``seen - t_handed`` per hundred of their mean
``seen - due``; prints the four, the eight phase means and what is left.
DESCRIPTIVE (``better`` is the manifest's convention)."""

from benchmarks import edge_join


def read(ctx):
    return edge_join.converge_edges_pct(ctx)
