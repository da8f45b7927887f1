"""Milliseconds from the registration instant to the FIRST resident's
status seen: one syncer's whole way from its ``Cluster`` create — the
controller's worker, importer, negotiation, syncer start, initial list,
first tick (and its compile), downstream create, the location's answer,
the status upsync — with nothing queued ahead of it."""

from benchmarks import sync_times


def read(ctx):
    got = sync_times.residents(ctx)
    if got is None or got[2] == len(got[1]):
        return None
    return got[1][0] * 1e3
